"""The FedBack round engine (paper Alg. 2) and its baseline instances.

One generic, jittable round program covers the whole algorithm family:

  ================  =========  ==========  ===============  ============
  algorithm         selection  dual λ      local prox ρ     aggregation
  ================  =========  ==========  ===============  ============
  fedback           fedback    ADMM        ρ (Eq. 2.3)      mean z_i^prev
  fedadmm           random     ADMM        ρ                mean z_i^prev
  admm (vanilla)    full       ADMM        ρ                mean z_i^prev
  fedavg            random     0           0                mean over I_s
  fedprox           random     0           μ (center ω)     mean over I_s
  ================  =========  ==========  ===============  ============

Client states are stacked pytrees (leading axis N); local training is a
``vmap`` of a scanned SGD prox solver; participation gates state commits
through ``tree_where`` masks so the whole round is one XLA program.

**Device-mesh scaling.**  Pass ``mesh=`` (a 1-D ``clients`` mesh from
``repro.sharding.clients.make_client_mesh``) and the same program shards
every client-stacked pytree — θ, λ, z_prev, controller vectors, data
shards — over the mesh: local solves run embarrassingly parallel across
devices, per-client trigger norms stay device-local, and the consensus
``ω = mean(z_i^prev)`` lowers to a cross-device all-reduce.  This is the
program shape ``repro.core.crosspod`` uses for pods, unified here for
the N-client simulation (shared algebra in ``repro.core.engine``).
Event decisions are bit-identical to the single-device engine (per-
client reductions never cross devices); ω matches within fp32 collective
reduction-order tolerance.

**Participation-proportional compute.**  With ``compact=True`` the
round's local-solve work scales with the controller's target rate L̄,
not with N: after selection, this round's *demand* — fresh trigger
events plus the deferral queue carried from earlier rounds — is
gathered into dense capacity-C buffers (C = ⌈slack·L̄·N⌉, per-device
under the mesh via ``shard_map``), the vmapped scanned SGD prox solver
runs over C rows of state *and data* instead of N, and committed rows
are scattered back.  Overflow is never dropped: it enters the
persistent ``DeferQueue`` (part of ``FLState``) with age-ordered,
starvation-free priority and is served in a later round
(``RoundMetrics.num_deferred`` is the queue length).  The per-round
commit limit additionally adapts to the controller's demand-load
estimate within [⌈L̄·N⌉, C] (``adaptive_capacity``; realized limit in
``RoundMetrics.realized_capacity``/``realized_slack``).  The dense path
(``compact=False``) runs all N solves behind a ``tree_where`` mask and
remains the bitwise reference for baselines; with ``capacity=N`` the
two paths agree (bit-identical events, fp32-tolerance state).  See
``repro.core.compact`` and docs/compaction.md.

**Stale-tolerant rounds.**  With ``max_staleness=S`` (None = the
synchronous engine) the round becomes a bounded-staleness pipeline: a
serviced solve lands in θ/λ/z_prev up to S rounds later (deterministic
per-client delay schedule in ``FLState.inflight``), while the consensus
average runs every round over the freshest available z-rows — Eq. 2.4
already tolerates stale rows by construction.  A client with an
in-flight solve is ineligible to re-fire (the eligibility mask threads
through compact planning), the controller measures *commit-time* events
through an issued-event ring buffer with a 1/(1+δ) feasible-rate
anti-windup clamp, and ``max_staleness=0`` reproduces the synchronous
engine bit for bit.  See docs/async.md.

**Ragged heterogeneous shards.**  Pass ``ragged=`` (a
``repro.utils.ragged.RaggedSpec``) and client data no longer needs
equal-size shards: all examples live in one pooled ``(Σnᵢ, ...)``
buffer and the solver gathers minibatches through each client's CSR
slice (``offsets[i] + local_idx``) — no per-client data rows are ever
materialized.  The dense path runs one vmapped solve per *size bucket*
(a few rectangular XLA programs, pad-to-bucket-capacity with masked
loss via ``engine.masked_batch_loss``); the compacted path streams CSR
slices through the capacity slots at the static ``max(nᵢ)`` scan shape
(masked when sizes differ).  Uniform sizes select the unmasked code
path *statically* and reproduce the rectangular dense and compact
engines bit for bit — events AND ω (tests/test_ragged.py and the
ragged golden trace pin this).  Composes with ``spec=`` (flat layout),
``compact=``, ``max_staleness=`` and ``mesh=`` (the pooled buffer is
replicated across devices; balance client *rows* onto the mesh with
``repro.sharding.clients.balanced_permutation``).

**Flat layout.**  Pass ``spec=`` (a ``repro.utils.flatstate.FlatSpec``
built from the params template) and θ, λ, z_prev live as contiguous
(N, D) fp32 matrices, ω as a (D,) vector: the trigger kernel reads the
state in place (no per-round concatenate copy) and the ADMM dual/center
algebra runs as ONE fused Pallas pass (``kernels.admm_update``,
``use_admm_kernel``) instead of separate λ/z/center HBM sweeps.  The
local solver unravels one (D,) row back into the model pytree inside
the vmap, so model code is layout-agnostic.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.sgd import sgd_init, sgd_step
from repro.utils.flatstate import FlatSpec
from repro.utils.ragged import RaggedSpec
from repro.utils.pytree import (
    tree_broadcast_like,
    tree_zeros_like,
)
from .compact import capacity_bounds, init_queue, make_compact_block, \
    shard_mapped_block
from .compress import check_mode, ef_consensus, ef_participant_mean, \
    init_residual
from .controller import ControllerConfig, init_controller
from .operands import RoundOperands
from .engine import (
    consensus_mean,
    dual_ascent,
    gated_commit,
    masked_batch_loss,
    measured_commits,
    participant_mean,
    participant_mean_loss,
    prox_center,
    record_issue,
    staleness_commit,
    staleness_masks,
)
from .selection import make_selection
from .state import FLState, InFlight, RoundMetrics, init_inflight
from .trigger import trigger_distances

ADMM_FAMILY = ("fedback", "fedadmm", "admm")
AVG_FAMILY = ("fedavg", "fedprox")


@dataclasses.dataclass(frozen=True)
class FLConfig:
    """Hyper-parameters of the federated optimization run."""

    algorithm: str = "fedback"
    n_clients: int = 100
    participation: float = 0.1  # L̄ (target rate / random fraction)
    rho: float = 0.01  # ADMM proximal parameter (Assumption 2)
    mu: float = 0.0  # FedProx proximal coefficient
    lr: float = 0.01
    momentum: float = 0.9
    epochs: int = 2
    batch_size: int = 42
    controller: ControllerConfig = ControllerConfig()
    trigger_metric: str = "l2"
    warm_start: bool = True  # init local solve at ω (paper footnote 2)
    selection: str | None = None  # override; defaults by algorithm
    use_trigger_kernel: bool | None = False  # Pallas trigger norms (l2);
    #                               explicit opt-in, None → auto (TPU)
    use_admm_kernel: bool | None = False  # fused λ⁺/center Pallas pass
    #            (flat layout only); explicit opt-in, None → auto (TPU)
    fused_gss: bool | None = False  # fused gather→ADMM→scatter commit on
    #            the compacted flat ADMM round (kernels/fused_gss.py):
    #            one pass over the (N, D) state instead of three.  The
    #            Pallas megakernel runs when ``use_admm_kernel`` also
    #            resolves on; otherwise the bit-identical jnp form
    #            carries the same fused dataflow.  Explicit opt-in,
    #            None → auto (TPU); ignored on dense rounds.
    compact: bool = False  # capacity-bounded compaction (core/compact.py)
    capacity_slack: float = 1.5  # C = ⌈slack·L̄·N⌉ solver rows per round
    capacity: int | None = None  # explicit global solver-row budget
    #            (fixes the per-round limit: adaptive capacity is only
    #             active when the budget is slack-derived)
    adaptive_capacity: bool = True  # per-round commit limit follows the
    #            demand-load estimate within [⌈L̄·N⌉, ⌈slack·L̄·N⌉]
    max_staleness: int | None = None  # stale-tolerant rounds: a serviced
    #            solve lands up to this many rounds later (per-client
    #            delay schedule; the consensus runs every round over the
    #            freshest available z-rows).  None = the synchronous
    #            engine (no pipeline state); 0 = the async pipeline with
    #            zero delay, which reproduces the synchronous engine bit
    #            for bit (the parity the tests pin down).
    staleness_schedule: str = "roundrobin"  # per-client delay draw, see
    #            repro.core.state.delay_schedule ("roundrobin"|"uniform")
    consensus_compress: str = "none"  # compressed consensus wire
    #            ("none"|"bf16"|"int8", core/compress.py): clients
    #            communicate quantized z-deltas with a persistent
    #            error-feedback residual (FLState.comm), so the
    #            consensus collective moves 2×/4× fewer bytes.  "none"
    #            keeps the exact uncompressed aggregation — bit-
    #            identical jaxprs, no residual state.  Flat layout
    #            (spec=) only.
    compress_block: int = 256  # per-block int8 scale granularity
    #            (coordinates per shared fp32 scale; clamped to D)
    state_backend: str = "device"  # where the (N, D) client matrices
    #            live ("device"|"host").  "device" is the bit-exact
    #            default: FLState on device, one jitted round program.
    #            "host" keeps θ/λ/z_prev/comm in host numpy buffers and
    #            streams only the (C, D) active-row working set per
    #            round (core/hoststate.py) — same events and fp32 state
    #            bits, device memory O(C·D) instead of O(N·D).
    #            Compact + flat layout only, single host (no mesh).
    stream_tiles: int = 2  # host backend: H2D chunks the (C, D) row
    #            stream is double-buffered into (copy/compute overlap
    #            granularity; never affects the solve width or bits)
    seed: int = 0

    def selection_name(self) -> str:
        if self.selection is not None:
            return self.selection
        if self.algorithm == "fedback":
            return "fedback"
        if self.algorithm == "admm":
            return "full"
        return "random"

    def local_rho(self) -> float:
        if self.algorithm in ADMM_FAMILY:
            return self.rho
        if self.algorithm == "fedprox":
            return self.mu
        return 0.0


def _ctrl_cfg(cfg: "FLConfig") -> ControllerConfig:
    """Controller config with L̄ defaulted from cfg.participation (a
    per-client array in cfg.controller.target_rate takes precedence).

    Any python scalar counts as "not per-client": an ``int`` target
    (e.g. ``target_rate=1``) must not silently bypass the defaulting.
    """
    c = cfg.controller
    if isinstance(c.target_rate, (bool, int, float)):
        c = c._replace(target_rate=float(cfg.participation))
    return c


def init_state(cfg: FLConfig, params0, *, mesh=None,
               client_axis: str = "clients",
               spec: FlatSpec | None = None) -> FLState:
    """Alg. 2 initialization: θ_i = z⁰, λ_i = 0, z_i^prev = θ_i, ω = z⁰.

    θ, z_prev and ω are materialized as *distinct* buffers (Alg. 2 sets
    them all from z⁰, but aliased or caller-owned buffers would break
    donating the state to the jitted round — donating ω must not delete
    the caller's ``params0``).  With ``mesh`` the stacked state is
    placed client-sharded across devices.  With ``spec`` the state is
    stored in the flat layout: θ/λ/z_prev as (N, D) fp32 matrices, ω as
    a (D,) vector (pass the same spec to ``make_round_fn``).
    """
    n = cfg.n_clients
    if cfg.state_backend not in ("device", "host"):
        raise ValueError(f"unknown state_backend: {cfg.state_backend!r} "
                         "(expected 'device' or 'host')")
    if cfg.state_backend == "host":
        from .hoststate import init_host_state
        if mesh is not None:
            raise ValueError("state_backend='host' is a single-host "
                             "backend (mesh must be None)")
        return init_host_state(cfg, params0, spec=spec)
    if check_mode(cfg.consensus_compress) != "none" and spec is None:
        raise ValueError(
            "consensus_compress="
            f"{cfg.consensus_compress!r} needs the flat (spec=) layout — "
            "the EF residual is an (N, D) matrix over the flat state")
    if spec is not None:
        params0 = spec.flatten(params0)
    theta = tree_broadcast_like(params0, n)
    z_prev = tree_broadcast_like(params0, n)  # separate buffers for donation
    ctrl = init_controller(n, _ctrl_cfg(cfg))
    inflight = None
    if cfg.max_staleness is not None:
        template = (spec.zeros_stacked(n) if spec is not None
                    else tree_zeros_like(theta))
        inflight = init_inflight(template, n, cfg.max_staleness,
                                 kind=cfg.staleness_schedule, seed=cfg.seed)
    comm = (init_residual(n, spec.dim)
            if cfg.consensus_compress != "none" else None)
    state = FLState(
        theta=theta,
        lam=tree_zeros_like(theta),
        z_prev=z_prev,
        omega=jax.tree.map(lambda x: jnp.array(x, copy=True), params0),
        ctrl=ctrl,
        rng=jax.random.PRNGKey(cfg.seed),
        round=jnp.zeros((), jnp.int32),
        queue=init_queue(n),
        inflight=inflight,
        comm=comm,
    )
    if mesh is not None:
        from repro.sharding.clients import check_divisible, fl_state_shardings
        check_divisible(n, mesh, axis=client_axis)
        state = jax.device_put(
            state, fl_state_shardings(mesh, axis=client_axis))
    return state


def _epoch_indices(rng, n_points: int, batch_size: int, epochs: int):
    """(steps, batch) gather indices covering `epochs` shuffled passes.

    The effective batch size is clamped to the shard size: with
    ``batch_size > n_points`` the old code produced a zero-length scan
    and ``jnp.mean([])`` → NaN train loss.
    """
    batch_size = min(batch_size, n_points)
    per_epoch = n_points // batch_size

    def one_epoch(key):
        perm = jax.random.permutation(key, n_points)
        return perm[: per_epoch * batch_size].reshape(per_epoch, batch_size)

    keys = jax.random.split(rng, epochs)
    return jax.vmap(one_epoch)(keys).reshape(epochs * per_epoch, batch_size)


def _local_solve(loss_fn, theta0, center, x, y, idx, *, rho, lr, momentum):
    """Inexact prox update (Eq. 2.3): SGD on f_i(θ) + ρ/2‖θ − c‖²."""
    vg = jax.value_and_grad(loss_fn)

    def body(carry, idx_b):
        params, opt = carry
        xb = jnp.take(x, idx_b, axis=0)
        yb = jnp.take(y, idx_b, axis=0)
        loss, g = vg(params, xb, yb)
        if rho:
            g = jax.tree.map(lambda gl, p, c: gl + rho * (p - c), g, params,
                             center)
        params, opt = sgd_step(params, g, opt, lr, momentum)
        return (params, opt), loss

    (theta, _), losses = jax.lax.scan(body, (theta0, sgd_init(theta0)), idx)
    return theta, jnp.mean(losses)


def _masked_local_solve(loss_fn, theta0, center, x, y, offset, size, idx,
                        *, rho, lr, momentum):
    """Inexact prox update over one ragged client's CSR slice.

    ``x``/``y`` are row buffers holding the client's slice at
    ``offset`` — the whole pooled (Σnᵢ, ...) buffer on the dense
    bucketed path, or the client's pre-sliced (max(nᵢ), ...) block
    (offset 0) on the compacted path.  ``idx`` holds virtual per-step
    indices in [0, bucket capacity).  Virtual rows beyond the client's
    ``size`` are padding: gathered clamped to the last real row (so
    every gather stays inside the client's CSR slice) and weighted 0
    in the per-example loss, so neither loss nor gradient sees them.
    A step whose batch is *all* padding is skipped outright — params,
    momentum and the reported mean loss are untouched — so a small
    client's solve equals a solve over only the steps that carry its
    data (no extra prox-pull toward the center, no 0-loss dilution of
    the train-loss metric).  With ``size == capacity`` every weight is
    1, no step skips, and the update equals :func:`_local_solve` on
    the same rows.
    """
    vg = jax.value_and_grad(
        lambda params, xb, yb, w: masked_batch_loss(loss_fn, params,
                                                    xb, yb, w))

    def body(carry, idx_b):
        params, opt = carry
        weights = (idx_b < size).astype(jnp.float32)
        live = jnp.sum(weights) > 0
        g_idx = offset + jnp.minimum(idx_b, size - 1)
        xb = jnp.take(x, g_idx, axis=0)
        yb = jnp.take(y, g_idx, axis=0)
        loss, g = vg(params, xb, yb, weights)
        if rho:
            g = jax.tree.map(lambda gl, p, c: gl + rho * (p - c), g, params,
                             center)
        new_params, new_opt = sgd_step(params, g, opt, lr, momentum)
        keep = lambda nw, od: jnp.where(live, nw, od)  # noqa: E731
        params = jax.tree.map(keep, new_params, params)
        opt = jax.tree.map(keep, new_opt, opt)
        return (params, opt), (loss, live)

    (theta, _), (losses, lives) = jax.lax.scan(
        body, (theta0, sgd_init(theta0)), idx)
    lives = lives.astype(jnp.float32)
    return theta, jnp.sum(losses * lives) / jnp.maximum(jnp.sum(lives), 1.0)


def _resolve_kernel_flag(flag: bool | None) -> bool:
    """None → auto: Pallas fast paths on TPU, jnp reference elsewhere
    (interpret-mode kernels validate the program but are slow on CPU)."""
    return jax.default_backend() == "tpu" if flag is None else flag


def _trigger(cfg: FLConfig, state: FLState, mesh, client_axis):
    """Per-client trigger distances; optionally the Pallas kernel path.

    Under the flat layout the kernel reads the (N, D) state in place
    (``trigger_sq_norms_pytree`` detects the single-matrix case)."""
    if _resolve_kernel_flag(cfg.use_trigger_kernel) \
            and cfg.trigger_metric == "l2":
        from repro.kernels import ops
        sq = ops.trigger_sq_norms_pytree(
            state.z_prev, state.omega, mesh=mesh, axis=client_axis)
        return jnp.sqrt(sq)
    return trigger_distances(state.omega, state.z_prev, cfg.trigger_metric)


def make_round_fn(cfg: FLConfig, loss_fn: Callable, data: dict[str, Any],
                  *, jit: bool = True, mesh=None,
                  client_axis: str = "clients", donate: bool | None = None,
                  ctrl_arg: bool = False, arrivals_arg: bool = False,
                  spec: FlatSpec | None = None,
                  ragged: RaggedSpec | None = None,
                  body_transform: Callable | None = None):
    """Build the per-round step.

    loss_fn(params, x_batch, y_batch) -> scalar mean loss.
    data: {"x": (N, n_i, ...), "y": (N, n_i)} — equal-size client
    shards; or, with ``ragged=``, the pooled {"x": (Σnᵢ, ...),
    "y": (Σnᵢ,)} buffers whose CSR layout the given
    ``repro.utils.ragged.RaggedSpec`` describes.

    mesh:   optional 1-D ``clients`` mesh; shards all client-stacked
            pytrees (state, data) over its axis and jits with explicit
            in/out shardings, turning the consensus mean into a
            cross-device all-reduce.
    donate: donate the input FLState buffers to the round (the state is
            produced fresh each round, so XLA can update it in place).
            Default: on for accelerator backends, off on CPU where
            donation is unimplemented and only warns.
    ctrl_arg: build ``round_fn(state, ctrl_overrides)`` instead, where
            ``ctrl_overrides`` is a dict of runtime controller-gain
            overrides (e.g. ``{"K": k, "target_rate": r}``) — the hook
            the batched sweep runner vmaps over.
    arrivals_arg: build ``round_fn(state, arrivals)`` instead (the
            serve step, ``repro.core.schedule``): ``arrivals`` is an
            (N,) bool *runtime* operand marking the clients whose
            updates reached the server this tick.  Fresh selection
            events are gated to arrived clients — the open-loop
            k-subset strategies draw among arrivals, the feedback
            trigger is masked and its integral law self-corrects —
            while plan eligibility is untouched, so demand already in
            the DeferQueue keeps being served whether or not the
            client re-arrives.  Arrival masks vary per call without
            retracing (one jitted program across the whole trace);
            with ``arrivals = ones(N)`` every tick, the step
            reproduces the plain round engine bit for bit — events
            AND fp32 ω (the degenerate-trace parity the serve tests
            pin).  Composes with ``ctrl_arg`` as
            ``round_fn(state, ctrl_overrides, arrivals)``.
    spec:   flat-layout codec (``repro.utils.flatstate.FlatSpec``); the
            state must come from ``init_state(..., spec=spec)``.  The
            given ``loss_fn`` still takes the model pytree — it is
            unravelled per client row inside the vmapped solver.

    ragged: CSR pooled-data spec (``repro.utils.ragged.RaggedSpec``);
            the local solver gathers minibatches through each client's
            CSR slice of the pooled buffer — size-bucketed vmapped
            solves on the dense path, slot-gathered slices at the
            static max(nᵢ) shape on the compacted path.  Uniform sizes
            reproduce the rectangular engines bit for bit.

    body_transform: optional wrapper applied to the finished round
            function *before* jit — ``round_fn = body_transform(
            round_fn)``.  The hook the static-analysis layer
            (``repro.analysis``) uses to count traces (retrace sentry)
            and to seed mutations in its self-tests; transforms must
            preserve the round signature.

    Returns round_fn(state[, ctrl_overrides]) -> (state, RoundMetrics).
    With ``jit`` it also has ``round_fn.lower(state, ...)``; the jitted
    program takes ``data`` as an argument, never as a constant baked
    into the executable (``repro.core.operands``).
    """
    if cfg.state_backend not in ("device", "host"):
        raise ValueError(f"unknown state_backend: {cfg.state_backend!r} "
                         "(expected 'device' or 'host')")
    if cfg.state_backend == "host":
        from .hoststate import make_host_round_fn
        return make_host_round_fn(
            cfg, loss_fn, data, jit=jit, mesh=mesh,
            client_axis=client_axis, donate=donate, ctrl_arg=ctrl_arg,
            arrivals_arg=arrivals_arg, spec=spec, ragged=ragged,
            body_transform=body_transform)
    n = cfg.n_clients
    if ragged is not None:
        if ragged.n_clients != n:
            raise ValueError(f"ragged spec describes {ragged.n_clients} "
                             f"clients, cfg.n_clients={n}")
        assert data["x"].shape[0] == ragged.buffer_rows, \
            (data["x"].shape, ragged.buffer_rows)
        # Static scan shape of slot-gathered (compacted) solves; the
        # dense path refines this per size bucket.
        n_points = ragged.max_size
    else:
        assert data["x"].shape[0] == n, (data["x"].shape, n)
        n_points = data["x"].shape[1]
    flat = spec is not None
    compress = check_mode(cfg.consensus_compress)
    if compress != "none" and not flat:
        raise ValueError(
            f"consensus_compress={compress!r} needs the flat (spec=) "
            "layout — the EF residual is an (N, D) matrix over the "
            "flat state")
    use_admm_kernel = flat and _resolve_kernel_flag(cfg.use_admm_kernel)
    select = make_selection(
        cfg.selection_name(),
        rate=cfg.participation,
        controller=_ctrl_cfg(cfg),
        metric=cfg.trigger_metric,
    )
    rho = cfg.local_rho()
    is_admm = cfg.algorithm in ADMM_FAMILY

    if mesh is not None:
        from repro.sharding.clients import (
            check_divisible,
            constrain_clients,
            fl_state_shardings,
            round_metrics_shardings,
            shard_client_data,
        )
        check_divisible(n, mesh, axis=client_axis)
        if ragged is None:
            data = shard_client_data(mesh, data, axis=client_axis)
        else:
            # The pooled buffer has no client-aligned leading axis: it
            # stays replicated; per-client offsets shard with the state.
            from repro.sharding.clients import replicate_data
            data = replicate_data(mesh, data)
        pin = partial(constrain_clients, mesh=mesh, axis=client_axis)
    else:
        pin = lambda t, **_: t  # noqa: E731
    # The jitted round takes the data as arguments, never as constants.
    data_shardings = (None if mesh is None else
                      jax.tree.map(lambda x: x.sharding, data))
    data = RoundOperands(data)

    solver = partial(_local_solve, loss_fn, rho=rho, lr=cfg.lr,
                     momentum=cfg.momentum)
    masked_solver = partial(_masked_local_solve, loss_fn, rho=rho,
                            lr=cfg.lr, momentum=cfg.momentum)
    if flat:
        # Convert at the solver boundary only: unflatten θ⁰/center once
        # per client, scan the SGD steps in native pytree space (same
        # per-step codegen as the tree layout), flatten the result.
        tree_solver = solver
        tree_masked_solver = masked_solver

        def solver(theta0_vec, center_vec, x, y, idx):
            theta, loss = tree_solver(spec.unflatten(theta0_vec),
                                      spec.unflatten(center_vec), x, y, idx)
            return spec.flatten(theta), loss

        def masked_solver(theta0_vec, center_vec, x, y, offset, size, idx):
            theta, loss = tree_masked_solver(
                spec.unflatten(theta0_vec), spec.unflatten(center_vec),
                x, y, offset, size, idx)
            return spec.flatten(theta), loss

    epoch_fn = partial(_epoch_indices, n_points=n_points,
                       batch_size=cfg.batch_size, epochs=cfg.epochs)

    fused = cfg.compact and is_admm and flat \
        and _resolve_kernel_flag(cfg.fused_gss)
    if cfg.fused_gss and not fused:
        raise ValueError(
            "fused_gss=True needs compact=True, an ADMM-family "
            "algorithm and the flat (spec=) layout — got "
            f"compact={cfg.compact}, algorithm={cfg.algorithm!r}, "
            f"flat={flat}")

    if cfg.compact:
        n_shards = mesh.shape[client_axis] if mesh is not None else 1
        c_min, cap = capacity_bounds(n, cfg.participation,
                                     cfg.capacity_slack, cfg.capacity,
                                     n_shards=n_shards)
        # An explicit budget pins the limit; adaptive capacity only
        # modulates the slack-derived one.
        adaptive = cfg.adaptive_capacity and cfg.capacity is None
        block = make_compact_block(solver, epoch_fn, cap, is_admm=is_admm,
                                   warm_start=cfg.warm_start,
                                   use_admm_kernel=use_admm_kernel,
                                   c_min=c_min, adaptive=adaptive,
                                   alpha=_ctrl_cfg(cfg).alpha,
                                   ragged=ragged,
                                   masked_solver=masked_solver,
                                   fused=fused,
                                   use_fused_kernel=(fused
                                                     and use_admm_kernel))
        if mesh is not None:
            block = shard_mapped_block(block, mesh, axis=client_axis,
                                       ragged=ragged is not None)

    async_mode = cfg.max_staleness is not None

    def _duals_and_centers(state):
        """λ⁺ and prox centers for every client (shared by the dense
        rectangular and dense ragged paths)."""
        if is_admm:
            if use_admm_kernel:
                from repro.kernels import ops
                lam_new, center = ops.admm_update(
                    state.theta, state.lam, state.omega, with_z=False,
                    mesh=mesh, axis=client_axis)
            else:
                lam_new = dual_ascent(state.lam, state.theta, state.omega)
                center = prox_center(state.omega, lam_new)
        else:
            lam_new = state.lam  # stays zero
            center = tree_broadcast_like(state.omega, n)
        return lam_new, center

    def dense_client_update(state, events, data_rng):
        """All-N solve behind the event mask (the bitwise baseline).

        Returns *service proposals* (θ_out, λ⁺, z) — the caller gates
        them into state (synchronous ``gated_commit``) or routes them
        through the delay pipeline (``staleness_commit``)."""
        lam_new, center = _duals_and_centers(state)
        theta_init = (tree_broadcast_like(state.omega, n) if cfg.warm_start
                      else state.theta)
        idx = jax.vmap(epoch_fn)(jax.random.split(data_rng, n))
        theta_out, losses = jax.vmap(solver)(
            pin(theta_init), pin(center), data["x"], data["y"], pin(idx))
        theta_out = pin(theta_out)

        z_new = (jax.tree.map(jnp.add, theta_out, lam_new) if is_admm
                 else theta_out)
        return theta_out, lam_new, z_new, losses

    # Per-bucket gather constants, staged once at build time.  The
    # traced round closes over them (they become jaxpr constants), so
    # no host→device transfer is staged inside the round — the
    # host-transfer rule in repro.analysis pins this down.
    if ragged is not None:
        _bucket_consts = tuple(
            (bucket,
             jnp.asarray(bucket.members, jnp.int32),
             jnp.asarray([ragged.offsets[i] for i in bucket.members],
                         jnp.int32),
             (jnp.asarray([ragged.sizes[i] for i in bucket.members],
                          jnp.int32) if bucket.padded else None))
            for bucket in ragged.buckets)

    # Shard-local bucket tables (dense ragged path under a mesh).
    # Bucket members interleave across the client axis, so a global
    # (θ, center)[members] gather crosses shard boundaries and SPMD
    # lowers it to 2·N·D·4 B of all-reduce per round (tracecheck, PR 6).
    # Instead each shard gets its OWN member table — per-shard local
    # row indices padded to the max local bucket population, shipped as
    # client-axis-sharded runtime operands so shard_map hands every
    # device its slice — and the bucket gathers/scatters never leave
    # the device.  Padded lanes clamp to local row 0 (always in
    # bounds), solve discarded work, and drop out of the scatter.
    if ragged is not None and mesh is not None:
        _n_shards = mesh.shape[client_axis]
        _n_local = n // _n_shards
        _local_tables = []
        for bucket in ragged.buckets:
            per_shard: list = [[] for _ in range(_n_shards)]
            for m in bucket.members:
                per_shard[m // _n_local].append(m % _n_local)
            cap_b = max(1, max(len(p) for p in per_shard))
            lmem = np.zeros((_n_shards, cap_b), np.int32)
            lval = np.zeros((_n_shards, cap_b), bool)
            for s, p in enumerate(per_shard):
                lmem[s, : len(p)] = p
                lval[s, : len(p)] = True
            _local_tables.append((jnp.asarray(lmem.reshape(-1)),
                                  jnp.asarray(lval.reshape(-1))))
        _local_tables = tuple(_local_tables)

        def _sharded_ragged_solve(theta_init, center, keys):
            from jax.sharding import PartitionSpec as P

            def body(theta_init, center, keys, offsets, sizes, x, y,
                     tables):
                n_loc = keys.shape[0]
                theta_out = theta_init
                losses = jnp.zeros((n_loc,), jnp.float32)
                for (bucket, *_), (lmem, lval) in zip(_bucket_consts,
                                                      tables, strict=True):
                    rows = jax.tree.map(lambda a, m=lmem: a[m],
                                        (theta_init, center))
                    offs = offsets[lmem]
                    bucket_epochs = partial(_epoch_indices,
                                            n_points=bucket.capacity,
                                            batch_size=cfg.batch_size,
                                            epochs=cfg.epochs)
                    idx_v = jax.vmap(bucket_epochs)(keys[lmem])

                    # Materialize each lane's CSR block as one
                    # contiguous slice (never ``take(pool, offset+idx)``
                    # inside the scan — that form miscompiles under
                    # shard_map on this jax; see core/compact.py).
                    def slice_rows(buf, o_=offs, ln=bucket.capacity):
                        return jax.vmap(
                            lambda o: jax.lax.dynamic_slice_in_dim(
                                buf, o, ln, 0))(o_)

                    x_rows, y_rows = slice_rows(x), slice_rows(y)
                    if bucket.padded:
                        th, ls = jax.vmap(masked_solver)(
                            rows[0], rows[1], x_rows, y_rows,
                            jnp.zeros_like(offs), sizes[lmem], idx_v)
                    else:
                        th, ls = jax.vmap(solver)(
                            rows[0], rows[1], x_rows, y_rows, idx_v)
                    drop = jnp.where(lval, lmem, n_loc)
                    theta_out = jax.tree.map(
                        lambda acc, r, d=drop: acc.at[d].set(
                            r.astype(acc.dtype), mode="drop"),
                        theta_out, th)
                    losses = losses.at[drop].set(ls, mode="drop")
                return theta_out, losses

            c, r = P(client_axis), P()
            mapped = jax.shard_map(
                body, mesh=mesh,
                in_specs=(c, c, c, c, c, r, r, c),
                out_specs=(c, c), check_vma=False)
            return mapped(theta_init, center, keys,
                          ragged.offsets_array(), ragged.sizes_array(),
                          data["x"], data["y"], _local_tables)

    def ragged_dense_update(state, events, data_rng):
        """All-N solve over pooled CSR data, one vmap per size bucket.

        Same service-proposal contract as ``dense_client_update``; the
        solver streams each client's minibatches straight out of the
        pooled buffer (global indices ``offset_i + local_idx``), so a
        uniform spec — one bucket, no padding — reproduces the
        rectangular dense path bit for bit.
        """
        lam_new, center = _duals_and_centers(state)
        theta_init = pin(tree_broadcast_like(state.omega, n)
                         if cfg.warm_start else state.theta)
        center = pin(center)
        keys = jax.random.split(data_rng, n)
        if mesh is not None:
            # Per-shard bucket solves: same per-client computation
            # (row, center, key, CSR slice all identical), gathered
            # through shard-local member tables under shard_map — the
            # only collective in the round stays the consensus mean.
            theta_out, losses = _sharded_ragged_solve(theta_init,
                                                      center, keys)
            theta_out = pin(theta_out)
            z_new = (jax.tree.map(jnp.add, theta_out, lam_new)
                     if is_admm else theta_out)
            return theta_out, lam_new, z_new, losses
        theta_out = theta_init  # every row overwritten below
        losses = jnp.zeros((n,), jnp.float32)
        for bucket, mem, offs, szs in _bucket_consts:
            rows = jax.tree.map(lambda a, m=mem: a[m],
                                (theta_init, center))
            bucket_epochs = partial(_epoch_indices,
                                    n_points=bucket.capacity,
                                    batch_size=cfg.batch_size,
                                    epochs=cfg.epochs)
            idx_v = jax.vmap(bucket_epochs)(keys[mem])
            if bucket.padded:
                th, ls = jax.vmap(
                    masked_solver, in_axes=(0, 0, None, None, 0, 0, 0))(
                    rows[0], rows[1], data["x"], data["y"], offs, szs,
                    idx_v)
            else:
                gidx = offs[:, None, None] + idx_v
                th, ls = jax.vmap(solver, in_axes=(0, 0, None, None, 0))(
                    rows[0], rows[1], data["x"], data["y"], gidx)
            theta_out = jax.tree.map(
                lambda acc, r, m=mem: acc.at[m].set(r.astype(acc.dtype)),
                theta_out, th)
            losses = losses.at[mem].set(ls)
        theta_out = pin(theta_out)
        z_new = (jax.tree.map(jnp.add, theta_out, lam_new) if is_admm
                 else theta_out)
        return theta_out, lam_new, z_new, losses

    # Dynamic-gather companions of the static CSR spec (the compact
    # plan indexes them by slot; client-stacked, so they shard with the
    # state under the mesh while the pooled buffer stays replicated).
    ragged_offsets = ragged.offsets_array() if ragged is not None else None
    ragged_sizes = ragged.sizes_array() if ragged is not None else None

    def compact_client_update(state, events, distances, eligible,
                              data_rng):
        """Gather demand rows into capacity slots, solve C rows, scatter."""
        keys = jax.random.split(data_rng, n)
        args = (events, distances, eligible, state.queue.age,
                state.queue.load, state.theta, state.lam,
                state.z_prev, state.omega, data["x"], data["y"], keys)
        if ragged is not None:
            args += (ragged_offsets, ragged_sizes)
        return block(*args)

    def round_body(state: FLState, ctrl_overrides, arrivals=None):
        rng, sel_rng, data_rng = jax.random.split(state.rng, 3)

        # --- server: trigger distances + selection --------------------
        distances = _trigger(cfg, state, mesh, client_axis)
        if async_mode:
            # A client with an in-flight solve is ineligible to re-fire
            # until its payload lands (one outstanding solve per client).
            inflight = state.inflight
            eligible = inflight.ttl == 0
            admit = eligible if arrivals is None else eligible & arrivals
            events = select.decide(sel_rng, state, distances,
                                   ctrl_overrides,
                                   eligible=admit) & admit
            ctrl = None  # stepped below on commit-time measurements
        elif arrivals is not None:
            # Serve step: fresh events only from this tick's arrivals.
            # Plan eligibility stays all-ones — deferred demand is
            # served whether or not the client re-arrives (a queued
            # client's work must never be dropped by a quiet tick).
            eligible = jnp.ones((n,), bool)
            events = select.decide(sel_rng, state, distances,
                                   ctrl_overrides,
                                   eligible=arrivals) & arrivals
            ctrl = select.measure(state.ctrl, events, ctrl_overrides)
        else:
            eligible = jnp.ones((n,), bool)
            events, ctrl = select(sel_rng, state, distances,
                                  ctrl_overrides=ctrl_overrides)

        # --- client-side computation (service proposals) --------------
        if cfg.compact:
            (theta_p, lam_p, z_p, q_age, q_load, serviced, losses,
             loss_mask, limits) = \
                compact_client_update(state, events, distances, eligible,
                                      data_rng)
            queue = state.queue._replace(age=q_age, load=q_load)
            # Σ over shards of the per-device commit limits (shape
            # (n_shards,) under the mesh, (1,) on a single device).
            realized_capacity = jnp.sum(limits)
            num_deferred = jnp.sum((q_age > 0).astype(jnp.int32))
        else:
            client_update = (ragged_dense_update if ragged is not None
                             else dense_client_update)
            theta_p, lam_p, z_p, losses = \
                client_update(state, events, data_rng)
            serviced, loss_mask = events, events
            queue = state.queue
            realized_capacity = jnp.asarray(n, jnp.int32)
            num_deferred = None  # 0 below (dense rounds never defer)

        # --- commit: synchronous gate or bounded-staleness pipeline ----
        if async_mode:
            land, direct, defer, new_ttl = staleness_masks(
                serviced, inflight.delay, inflight.ttl)
            theta, park_theta = staleness_commit(
                state.theta, theta_p, inflight.theta, land, direct, defer)
            lam, park_lam = staleness_commit(
                state.lam, lam_p, inflight.lam, land, direct, defer)
            z_prev, park_z = staleness_commit(
                state.z_prev, z_p, inflight.z, land, direct, defer)
            z_prev = pin(z_prev)
            committed = direct | land
            # Commit-time participation accounting: the controller
            # measures an issue δ_i rounds after the fact, with the
            # feasible-rate ceiling as anti-windup.
            hist = record_issue(inflight.hist, events, state.round)
            measured = measured_commits(hist, inflight.delay, state.round)
            ctrl = select.measure(state.ctrl, measured, ctrl_overrides,
                                  staleness_delay=inflight.delay)
            new_inflight = InFlight(delay=inflight.delay, ttl=new_ttl,
                                    theta=park_theta, lam=park_lam,
                                    z=park_z, hist=hist)
            num_inflight = jnp.sum((new_ttl > 0).astype(jnp.int32))
            num_landed = jnp.sum(land.astype(jnp.int32))
            if num_deferred is None:
                num_deferred = jnp.zeros((), jnp.int32)
        elif cfg.compact:
            theta, lam, z_prev = theta_p, lam_p, pin(z_p)
            committed, new_inflight = serviced, state.inflight
            num_inflight = num_landed = jnp.zeros((), jnp.int32)
        else:
            theta = gated_commit(events, theta_p, state.theta)
            lam = gated_commit(events, lam_p, state.lam)
            z_prev = pin(gated_commit(events, z_p, state.z_prev))
            committed, new_inflight = events, state.inflight
            num_inflight = num_landed = jnp.zeros((), jnp.int32)

        # --- server-side aggregation -----------------------------------
        num_events = jnp.sum(events.astype(jnp.int32))
        num_committed = jnp.sum(committed.astype(jnp.int32))
        if num_deferred is None:
            num_deferred = num_events - num_committed
        comm = state.comm
        if is_admm:
            # ω^{k+1} = (1/N) Σ_i z_i^prev — stale entries included
            # (Eq. 2.4); under staleness the freshest *available* rows.
            if compress != "none":
                omega, comm = ef_consensus(
                    z_prev, state.omega, comm, mode=compress,
                    block=cfg.compress_block, mesh=mesh, axis=client_axis)
            else:
                omega = consensus_mean(z_prev)
        else:
            # FedAvg/FedProx: non-weighted mean over participants only.
            # (z_prev carries this round's committed uploads; stale rows
            # are masked out by ``committed``.)
            if compress != "none":
                omega, comm = ef_participant_mean(
                    z_prev, committed, state.omega, comm, num_committed,
                    mode=compress, block=cfg.compress_block, mesh=mesh,
                    axis=client_axis)
            else:
                omega = participant_mean(z_prev, committed, state.omega,
                                         num_events=num_committed)

        rate_floor = cfg.participation * n
        metrics = RoundMetrics(
            events=events,
            num_events=num_events,
            distances=distances,
            delta=ctrl.delta,
            load=ctrl.load,
            train_loss=participant_mean_loss(losses, loss_mask),
            num_deferred=num_deferred,
            realized_capacity=realized_capacity,
            realized_slack=(realized_capacity.astype(jnp.float32)
                            / (rate_floor if rate_floor > 0 else 1.0)),
            num_inflight=num_inflight,
            num_landed=num_landed,
            committed=committed,
        )
        new_state = FLState(theta=theta, lam=lam, z_prev=z_prev, omega=omega,
                            ctrl=ctrl, rng=rng, round=state.round + 1,
                            queue=queue, inflight=new_inflight, comm=comm)
        return new_state, metrics

    if ctrl_arg and arrivals_arg:
        round_fn = round_body
    elif ctrl_arg:
        def round_fn(state, ctrl_overrides):
            return round_body(state, ctrl_overrides)
    elif arrivals_arg:
        def round_fn(state, arrivals):
            return round_body(state, None, arrivals)
    else:
        def round_fn(state):
            return round_body(state, None)

    if body_transform is not None:
        round_fn = body_transform(round_fn)

    if not jit:
        return round_fn

    # Donation is safe now that init_state materializes z_prev separately
    # from θ; CPU has no donation support and would warn on every call.
    if donate is None:
        donate = jax.default_backend() != "cpu"
    donate_argnums = (0,) if donate else ()

    if mesh is None:
        return data.jit(round_fn, donate_argnums=donate_argnums)

    from jax.sharding import NamedSharding, PartitionSpec
    state_sh = fl_state_shardings(mesh, axis=client_axis)
    metrics_sh = round_metrics_shardings(mesh, axis=client_axis)
    in_sh: tuple = (state_sh,)
    if ctrl_arg:
        in_sh += (None,)
    if arrivals_arg:
        in_sh += (NamedSharding(mesh, PartitionSpec(client_axis)),)
    return data.jit(round_fn, in_shardings=in_sh,
                    out_shardings=(state_sh, metrics_sh),
                    donate_argnums=donate_argnums,
                    operand_shardings=data_shardings)


def make_eval_fn(loss_and_acc_fn: Callable, *, jit: bool = True,
                 spec: FlatSpec | None = None):
    """loss_and_acc_fn(params, x, y) -> (loss, accuracy) on the server ω.

    With ``spec`` (flat layout) the flat ω is unravelled back into the
    model pytree before evaluation.
    """

    def eval_fn(state: FLState, x, y):
        omega = spec.unflatten(state.omega) if spec is not None \
            else state.omega
        return loss_and_acc_fn(omega, x, y)

    return jax.jit(eval_fn) if jit else eval_fn


def run_rounds(round_fn, state: FLState, num_rounds: int):
    """Python-loop driver returning stacked per-round metrics.

    Metrics stay on device until the final stack — the loop never calls
    ``device_get``, so each ``round_fn`` dispatch is asynchronous and
    donation/async dispatch pipeline across rounds.  The returned
    metrics pytree has leaves of shape (num_rounds, ...); fetch to host
    once at the end (``jax.device_get``/``np.asarray``) if needed.
    """
    history = []
    for _ in range(num_rounds):
        state, m = round_fn(state)
        history.append(m)
    metrics = (jax.tree.map(lambda *xs: jnp.stack(xs), *history)
               if history else None)
    return state, metrics
