"""Rounds-as-a-service: the event-driven admission scheduler.

The round engine (``repro.core.fedback``) beats on a fixed cadence —
every client that wants to participate waits for the next round
boundary.  This module replaces the outer loop with an event-driven
scheduler in the continuous-batching style: client updates *arrive* on
a generated trace (:func:`make_trace` — Poisson / diurnal / bursty /
the degenerate "everyone fires every tick"), are admitted into free
capacity slots immediately through the existing ``CompactPlan`` +
``DeferQueue`` machinery (overflow defers, never drops), and the
consensus mean ticks on its own clock — every tick averages the
freshest available z-rows, however few clients arrived.

The inner step stays ONE jitted program: ``make_round_fn(...,
arrivals_arg=True)`` takes the tick's (N,) bool arrival mask as a
runtime operand, so the whole trace runs through a single compiled
round (the retrace sentry in ``repro.analysis`` pins this).  The host
loop (:func:`serve`) only drains the trace, fetches the tick's commit
mask and stamps wall-clock times; :class:`ServeReport` carries p50/p99
admission→commit latency and sustained commits/sec (the
``BENCH_serve.json`` artifact, gated in ``benchmarks/compare.py``).

**Parity anchor.**  The all-ones trace makes every tick a synchronous
round: fresh events are masked by ``& ones`` (a no-op) and the
k-subset strategies draw among "everyone" — the serve step reproduces
the plain round engine bit for bit, events AND fp32 ω
(tests/test_serve.py pins the {uniform,ragged} × {1,2}-device matrix).

See docs/serving.md.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.spans import gc_spans, span

from .fedback import stack_metrics

TRACE_KINDS = ("sync", "poisson", "diurnal", "bursty")


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Generator spec for a (ticks, N) boolean arrival trace.

    ``sync``     everyone arrives every tick — the degenerate trace
                 that reproduces the synchronous round engine.
    ``poisson``  i.i.d. Bernoulli(rate) per client-tick (the Poisson
                 process thinned onto the tick grid).
    ``diurnal``  Bernoulli with a sinusoidal rate, period ``period``
                 ticks and relative amplitude ``amplitude``.
    ``bursty``   quiet Bernoulli(rate·quiet_frac) baseline; every
                 ``burst_every`` ticks a ``burst_len``-tick burst at
                 Bernoulli(burst_rate) — the flash-crowd adversary the
                 DeferQueue absorbs.
    """

    kind: str = "poisson"
    n_clients: int = 64
    ticks: int = 64
    rate: float = 0.5  # per-tick arrival probability (mean load)
    seed: int = 0
    period: int = 24  # diurnal period, ticks
    amplitude: float = 0.9  # diurnal relative swing, in [0, 1]
    quiet_frac: float = 0.25  # bursty baseline = rate · quiet_frac
    burst_every: int = 16
    burst_len: int = 4
    burst_rate: float = 0.9


def make_trace(cfg: TraceConfig) -> np.ndarray:
    """(ticks, N) bool arrival mask; deterministic per seed."""
    if cfg.kind not in TRACE_KINDS:
        raise ValueError(f"unknown trace kind {cfg.kind!r}; "
                         f"expected one of {TRACE_KINDS}")
    t, n = cfg.ticks, cfg.n_clients
    if cfg.kind == "sync":
        return np.ones((t, n), bool)
    rng = np.random.default_rng(cfg.seed)
    if cfg.kind == "poisson":
        rates = np.full((t,), cfg.rate)
    elif cfg.kind == "diurnal":
        phase = 2.0 * np.pi * np.arange(t) / max(cfg.period, 1)
        rates = cfg.rate * (1.0 + cfg.amplitude * np.sin(phase))
    else:  # bursty
        rates = np.full((t,), cfg.rate * cfg.quiet_frac)
        for start in range(0, t, max(cfg.burst_every, 1)):
            rates[start: start + cfg.burst_len] = cfg.burst_rate
    rates = np.clip(rates, 0.0, 1.0)
    return rng.random((t, n)) < rates[:, None]


def sync_trace(n_clients: int, ticks: int) -> np.ndarray:
    """The degenerate "everyone fires every tick" parity trace."""
    return make_trace(TraceConfig(kind="sync", n_clients=n_clients,
                                  ticks=ticks))


@dataclasses.dataclass
class ServeReport:
    """What the serve loop observed: admissions, commits, latencies.

    *Admission* is the tick a client's arrival fired an event (the
    server accepted the update for service); *commit* is the tick its
    θ/λ/z_prev row actually landed (same tick on the dense synchronous
    path; later under capacity deferral and/or staleness).  One
    latency sample per admission→commit pair, earliest admission kept
    when a pending client re-fires.  Wall-clock latency spans the
    admission tick's dispatch to the commit tick's observed completion
    (the host fetch), so it includes everything a client would wait
    for; compile time is excluded only when the loop is warmed up
    (``serve(..., warmup=True)``).
    """

    ticks: int
    n_clients: int
    arrivals_total: int          # Σ trace — raw arrival opportunities
    admitted_total: int          # admission events (latency starts)
    commits_total: int           # committed rows (latency stops)
    pending_final: int           # still queued/in-flight at the end
    conservation_ok: bool        # admitted − commits == pending, and
    #                              pending == deferred + in-flight (the
    #                              engine-side queue/pipeline agree)
    latency_ticks: np.ndarray    # (commits_total,) int
    latency_us: np.ndarray       # (commits_total,) float
    wall_s: float                # whole-trace wall time
    final_num_deferred: int
    final_num_inflight: int

    @property
    def commits_per_sec(self) -> float:
        return self.commits_total / max(self.wall_s, 1e-12)

    @property
    def ticks_per_sec(self) -> float:
        return self.ticks / max(self.wall_s, 1e-12)

    def percentiles(self, q=(50, 99)) -> dict:
        out: dict = {}
        for name, arr in (("ticks", self.latency_ticks),
                          ("us", self.latency_us)):
            for p in q:
                key = f"p{p}_latency_{name}"
                out[key] = (float(np.percentile(arr, p))
                            if arr.size else 0.0)
        return out

    def summary(self) -> dict:
        """JSON-able digest (the BENCH_serve.json section body)."""
        return {
            "ticks": self.ticks,
            "n_clients": self.n_clients,
            "arrivals_total": self.arrivals_total,
            "admitted_total": self.admitted_total,
            "commits_total": self.commits_total,
            "pending_final": self.pending_final,
            "conservation_ok": self.conservation_ok,
            **self.percentiles(),
            "commits_per_sec": self.commits_per_sec,
            "ticks_per_sec": self.ticks_per_sec,
            "wall_s": self.wall_s,
            "final_num_deferred": self.final_num_deferred,
            "final_num_inflight": self.final_num_inflight,
        }


def _copy_state(state):
    return jax.tree.map(lambda x: jnp.array(x, copy=True)
                        if isinstance(x, jax.Array) else x, state)


def serve(round_fn, state, trace, *, warmup: bool = False,
          collect_metrics: bool = False):
    """Drain an arrival trace through the jitted serve step.

    ``round_fn`` must come from ``make_round_fn(...,
    arrivals_arg=True)``; ``trace`` is a (ticks, N) bool array.  Per
    tick the host converts one arrival row to a device array, steps
    the program and fetches the tick's ``committed`` mask plus the
    scalar queue/pipeline depths — nothing else crosses the host
    boundary, so the step itself stays transfer-free (the tracecheck
    ``host-transfer-budget`` rule inspects it).

    ``warmup=True`` compiles the step on a deep copy of ``state``
    before timing starts (safe under donation — only the copy's
    buffers are consumed), so wall-clock latencies exclude compile.

    Each tick is a ``fedback/serve.tick`` host span (arg ``t``) holding
    ``serve.upload``, ``serve.step``, ``serve.fetch`` (ends where the
    commit is stamped) and ``serve.ledger`` (args ``committed`` and
    ``deferred``: the tick's commits and queue length); each collector
    pass is ``fedback/gc`` (``repro.utils.spans``).

    Returns ``(state, ServeReport)`` — or ``(state, report, history)``
    with ``collect_metrics=True``, where ``history`` is the list of
    per-tick ``RoundMetrics`` (host copies).
    """
    trace = np.asarray(trace, bool)
    ticks, n = trace.shape
    if warmup and ticks:
        probe = round_fn(_copy_state(state),
                         jnp.zeros((n,), bool))
        jax.block_until_ready(probe)
        del probe

    pending_tick = np.full((n,), -1, np.int64)
    pending_wall = np.zeros((n,), np.float64)
    latency_ticks: list = []
    latency_us: list = []
    admitted_total = 0
    commits_total = 0
    history: list = []
    final_deferred = final_inflight = 0

    with gc_spans():
        t_begin = time.perf_counter()
        for t in range(ticks):
            with span("serve.tick", t=t):
                t_dispatch = time.perf_counter()
                with span("serve.upload"):
                    arrivals = jnp.asarray(trace[t])
                with span("serve.step"):
                    state, metrics = round_fn(state, arrivals)
                with span("serve.fetch"):
                    events = np.asarray(metrics.events)
                    committed = np.asarray(metrics.committed)
                    t_done = time.perf_counter()
                with span("serve.ledger") as ledger:
                    if collect_metrics:
                        history.append(jax.device_get(metrics))
                    final_deferred = int(metrics.num_deferred)
                    final_inflight = int(metrics.num_inflight)
                    ledger.set_metadata(committed=int(committed.sum()),
                                        deferred=final_deferred)

                    # Demand is one bit per client: a commit closes the
                    # *earliest* open admission, and a re-fire while
                    # pending (or on the very tick the commit lands)
                    # merges into it — exactly the DeferQueue's
                    # events|age semantics, so no extra admission.
                    was_pending = pending_tick >= 0
                    landed = committed & was_pending
                    for i in np.nonzero(landed)[0]:
                        latency_ticks.append(t - pending_tick[i])
                        latency_us.append((t_done - pending_wall[i]) * 1e6)
                        pending_tick[i] = -1
                    commits_total += int(landed.sum())

                    fresh = events & ~was_pending
                    admitted_total += int(fresh.sum())
                    # Same-tick service: admitted and committed in one
                    # step.
                    instant = fresh & committed
                    for _ in range(int(instant.sum())):
                        latency_ticks.append(0)
                        latency_us.append((t_done - t_dispatch) * 1e6)
                    commits_total += int(instant.sum())
                    opened = fresh & ~instant
                    pending_tick[opened] = t
                    pending_wall[opened] = t_dispatch
        wall_s = time.perf_counter() - t_begin

    pending_final = int((pending_tick >= 0).sum())
    report = ServeReport(
        ticks=ticks,
        n_clients=n,
        arrivals_total=int(trace.sum()),
        admitted_total=admitted_total,
        commits_total=commits_total,
        pending_final=pending_final,
        conservation_ok=(admitted_total - commits_total == pending_final
                         and pending_final
                         == final_deferred + final_inflight),
        latency_ticks=np.asarray(latency_ticks, np.int64),
        latency_us=np.asarray(latency_us, np.float64),
        wall_s=wall_s,
        final_num_deferred=final_deferred,
        final_num_inflight=final_inflight,
    )
    if collect_metrics:
        return state, report, history
    return state, report


def run_trace(round_fn, state, trace):
    """Device-side trace driver (no latency accounting): step every
    tick, stack the metrics to the host (``stack_metrics``) — the serve
    analogue of ``run_rounds`` (golden traces and parity tests use it)."""
    history = []
    for t in range(np.asarray(trace).shape[0]):
        state, m = round_fn(state, jnp.asarray(np.asarray(trace)[t]))
        history.append(m)
    return state, stack_metrics(history)


__all__ = [
    "TRACE_KINDS",
    "TraceConfig",
    "make_trace",
    "sync_trace",
    "ServeReport",
    "serve",
    "run_trace",
]
