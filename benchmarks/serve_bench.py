"""Serving-engine benchmark → BENCH_serve.json.

Measures the rounds-as-a-service scheduler (``repro.core.schedule``)
and pins its two contracts:

* ``serve_bursty`` — a bursty arrival trace (flash crowds over a quiet
  baseline) through the compacted serve step: p50/p99 admission→commit
  latency in ticks (deterministic per seed) and in wall-clock µs,
  plus sustained commits/sec and ticks/sec.  Wall-clock keys gate
  under the env-fingerprint guard in ``benchmarks/compare.py``; the
  deterministic keys (tick latencies, counts, ``conservation_ok``)
  gate unconditionally.
* ``serve_parity`` — the degenerate "everyone fires every tick" trace
  must reproduce the synchronous round engine bit for bit: events AND
  fp32 ω.  ``serve_parity_bitexact`` is gated unconditionally.

Run with ``BENCH_DIR=benchmarks/baselines`` to regenerate the
committed baseline intentionally.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from repro.core.fedback import init_state, make_round_fn, run_rounds
from repro.core.schedule import TraceConfig, make_trace, run_trace, serve, \
    sync_trace
from repro.launch.serve_fl import build_serve_problem

BENCH_DIR = os.environ.get("BENCH_DIR", ".")

#: Bursty-trace workload (big enough that per-tick work dominates the
#: host loop, small enough for the nightly CPU runner).
N_CLIENTS = 256
TICKS = 96
RATE = 0.25
PARITY_N = 64
PARITY_TICKS = 12


def _env_fingerprint() -> str:
    import platform
    return (f"jax={jax.__version__};backend={jax.default_backend()};"
            f"machine={platform.machine()}")


def bench_bursty(report: dict) -> None:
    cfg, round_fn, state = build_serve_problem(
        N_CLIENTS, participation=RATE, compact=True)
    trace = make_trace(TraceConfig(
        kind="bursty", n_clients=N_CLIENTS, ticks=TICKS, rate=RATE,
        seed=0))
    state, rep = serve(round_fn, state, trace, warmup=True)
    report["serve_bursty"] = rep.summary()
    print(f"serve_bursty: N={N_CLIENTS} ticks={TICKS} "
          f"p50={rep.percentiles()['p50_latency_ticks']:.1f}t "
          f"p99={rep.percentiles()['p99_latency_ticks']:.1f}t "
          f"{rep.commits_per_sec:.0f} commits/s "
          f"conservation={'ok' if rep.conservation_ok else 'VIOLATED'}")


def bench_parity(report: dict) -> None:
    """Degenerate trace vs the synchronous round engine, bit for bit."""
    cfg, serve_fn, s_serve = build_serve_problem(
        PARITY_N, participation=RATE, compact=True)
    from repro.data.synthetic import make_least_squares
    from repro.utils.flatstate import make_flat_spec
    data, params0, loss_fn = make_least_squares(
        PARITY_N, n_points=8, dim=16, seed=0)
    spec = make_flat_spec(params0)
    sync_fn = make_round_fn(cfg, loss_fn, data, spec=spec)
    s_sync = init_state(cfg, params0, spec=spec)

    s_serve, m_serve = run_trace(serve_fn, s_serve,
                                 sync_trace(PARITY_N, PARITY_TICKS))
    s_sync, m_sync = run_rounds(sync_fn, s_sync, PARITY_TICKS)
    events_ok = bool(np.array_equal(np.asarray(m_serve.events),
                                    np.asarray(m_sync.events)))
    omega_ok = bool(np.array_equal(np.asarray(s_serve.omega),
                                   np.asarray(s_sync.omega)))
    report["serve_parity"] = {
        "serve_parity_bitexact": events_ok and omega_ok,
        "events_bitexact": events_ok,
        "omega_bitexact": omega_ok,
        "ticks": PARITY_TICKS,
        "n_clients": PARITY_N,
    }
    print(f"serve_parity: events={'ok' if events_ok else 'MISMATCH'} "
          f"omega={'ok' if omega_ok else 'MISMATCH'}")


def main() -> None:
    report: dict = {"_env": _env_fingerprint()}
    bench_bursty(report)
    bench_parity(report)
    out = os.path.join(BENCH_DIR, "BENCH_serve.json")
    with open(out, "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print(f"wrote {out}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
