"""Host-offloaded streaming round engine (``state_backend="host"``).

The compact engine (core/compact.py) made per-round solver *compute*
∝ C = ⌈slack·L̄·N⌉, but the device backend still materializes every
(N, D) row of θ/λ/z_prev (and the EF residual ``comm``) in device
memory — footprint ∝ N, not ∝ the participation rate the FedBack
controller is explicitly driving down.  This module keeps the
client-stacked matrices in host ``numpy`` buffers (``HostState``) and
runs each round as three jitted device programs glued by host-side
row gathers/scatters:

1. **plan** — full-N but O(N)-vector work: PRNG split, selection,
   ``compact_plan``, queue update, (async) staleness masks and the
   commit-time controller step.  In/out: only (N,) vectors and the
   (C,) slot indices.  The (C, 2) slot PRNG keys stay on device.
2. **solve** — the (C, D) working set.  The host gathers the C active
   θ/λ rows out of its buffers with fancy indexing, streams them up as
   ``stream_tiles`` double-buffered ``jax.device_put`` tiles (puts are
   dispatched back-to-back, so copy t+1 overlaps the device consuming
   copy t; the tiles are donated — they are jax-owned copies, the host
   buffers stay the source of truth), and the program concatenates
   them back to the full capacity width C before the vmapped solve —
   concatenation is exact, so the solve runs at the *same* vmap width
   as the device block and is bit-identical to it.  Training data
   (rectangular (N, n, ...) or the pooled CSR buffer) is round-static
   and stays device-resident; the program gathers/slices it by slot
   index exactly like ``make_compact_block``.
3. **aggregate** — ONE full-width server pass per round: ``device_put``
   the scattered z_prev (and ``comm``), compute the consensus mean (or
   EF-compressed consensus) *and* the next round's trigger distances
   ‖ω_{k+1} − z_i‖ in the same program.  Consensus and trigger both
   read all N rows — Ω(N·D) server work the roofline already prices —
   so fusing them halves the full-width H2D traffic; the distances are
   cached on ``HostState.distances`` for the next plan step.

Results come back with a D2H fetch of the three (C, D) row matrices
and are scattered into the host buffers in place (numpy fancy-index
assignment at the valid slots' distinct client ids ≡ the device
``scatter_rows`` drop-scatter).  Under bounded staleness the commit
routes rows through the host-resident park buffers exactly like
``engine.staleness_commit`` (land: park→state copy; direct: slot
row→state; defer: slot row→park; serviced clients are ttl==0, so land
and serviced are disjoint).

**Bit-exactness.**  The device path stays the default and the parity
oracle.  Host == device bit for bit (events AND fp32 ω/θ/λ/z_prev)
because every device computation runs the same jnp ops at the same
shapes on the same values: selection/plan math is identical, the solve
runs at width C like the block, host gather/scatter moves exact fp32
rows, and XLA CPU/TPU reductions are run-to-run deterministic for a
given op shape.  Host-side numpy never *computes* — it only copies
rows — precisely because numpy and XLA reduction orders differ.

Per-round transfer budget (priced by the tracecheck
``host-transfer-budget`` rule): row-stream legs 2·C·D·4 B up +
3·C·D·4 B down (≤ the budgeted 8·C·D·4), one full-width server leg
N·D·4 B up (×2 with ``comm``, +N·D·4 down for the residual), and O(N)
bytes of plan vectors.  Persistent *device* state between rounds is
O(C·D) working set↔0 (transient) + O(N) vectors + the (D,) ω —
``HostState.device_state_bytes`` / ``host_state_bytes`` report both.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.flatstate import FlatSpec
from repro.utils.spans import span

from .compact import (
    adaptive_limit,
    capacity_bounds,
    compact_plan,
    init_queue,
    queue_update,
)
from .compress import check_mode, ef_consensus, ef_participant_mean
from .controller import init_controller
from .engine import (
    consensus_mean,
    dual_ascent,
    measured_commits,
    participant_mean,
    participant_mean_loss,
    prox_center,
    record_issue,
    staleness_masks,
)
from .fedback import (
    ADMM_FAMILY,
    _ctrl_cfg,
    _epoch_indices,
    _local_solve,
    _masked_local_solve,
    _resolve_kernel_flag,
)
from .operands import RoundOperands
from .selection import make_selection
from .state import (
    DeferQueue,
    FLState,
    HostState,
    InFlight,
    RoundMetrics,
    delay_schedule,
)
from .trigger import trigger_distances


class _PlanView(NamedTuple):
    """The slice of FLState the selection strategies actually read
    (``decide`` touches only ``state.ctrl`` and ``state.round``)."""

    ctrl: Any
    round: Any


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise ValueError(f"state_backend='host' {what}")


def init_host_state(cfg, params0, *, spec: FlatSpec) -> HostState:
    """Host-buffer twin of ``init_state``: same values, (N, D) matrices
    in host numpy.  ``distances`` starts lazy (None) — the first round
    fills it with one trigger pass, so init itself never touches the
    device with an (N, D) operand."""
    _require(spec is not None, "needs the flat (spec=) layout")
    _require(cfg.compact, "needs compact=True (the streaming round is "
             "built on the CompactPlan slot indices)")
    n = cfg.n_clients
    compress = check_mode(cfg.consensus_compress)
    flat0 = np.asarray(spec.flatten(params0))  # (D,) fp32
    inflight = None
    if cfg.max_staleness is not None:
        inflight = InFlight(
            delay=delay_schedule(n, cfg.max_staleness,
                                 kind=cfg.staleness_schedule,
                                 seed=cfg.seed),
            ttl=jnp.zeros((n,), jnp.int32),
            theta=spec.zeros_stacked_host(n),
            lam=spec.zeros_stacked_host(n),
            z=spec.zeros_stacked_host(n),
            hist=jnp.zeros((n, cfg.max_staleness + 1), bool),
        )
    return HostState(
        theta=spec.host_broadcast_rows(flat0, n),
        lam=spec.zeros_stacked_host(n),
        z_prev=spec.host_broadcast_rows(flat0, n),
        omega=jnp.asarray(flat0),
        ctrl=init_controller(n, _ctrl_cfg(cfg)),
        rng=jax.random.PRNGKey(cfg.seed),
        round=jnp.zeros((), jnp.int32),
        queue=init_queue(n),
        distances=None,
        inflight=inflight,
        comm=(spec.zeros_stacked_host(n) if compress != "none" else None),
    )


def host_state_from_tree(tree: FLState, cfg, *, spec: FlatSpec) -> HostState:
    """Rebuild a ``HostState`` from an FLState-shaped checkpoint tree.

    Leaves may be numpy (a host-backend checkpoint read straight off
    disk) or device arrays (a device-backend state being migrated):
    the (N, D) matrices land in host numpy buffers, the O(N) vectors
    on device.  ``distances`` is left lazy — recomputed by the first
    round — so restoring never stages an (N, D) device transfer.
    """
    _require(spec is not None, "needs the flat (spec=) layout")

    def mat(x) -> np.ndarray:
        return np.array(x, np.float32, copy=True)  # writable host buffer

    inflight = None
    if tree.inflight is not None:
        f = tree.inflight
        inflight = InFlight(delay=jnp.asarray(f.delay),
                            ttl=jnp.asarray(f.ttl),
                            theta=mat(f.theta), lam=mat(f.lam),
                            z=mat(f.z), hist=jnp.asarray(f.hist))
    return HostState(
        theta=mat(tree.theta), lam=mat(tree.lam), z_prev=mat(tree.z_prev),
        omega=jnp.asarray(tree.omega),
        ctrl=jax.tree.map(jnp.asarray, tree.ctrl),
        rng=jnp.asarray(tree.rng),
        round=jnp.asarray(tree.round),
        queue=jax.tree.map(jnp.asarray, tree.queue),
        distances=None,
        inflight=inflight,
        comm=(None if tree.comm is None else mat(tree.comm)),
    )


def host_state_to_device(host: HostState) -> FLState:
    """Materialize a device-backend ``FLState`` from host buffers (the
    host→device resume path; the one place an (N, D) upload of every
    field is the *point*)."""
    return jax.tree.map(jnp.asarray, host.to_checkpoint_tree())


def _tile_spans(capacity: int, tiles: int) -> tuple[tuple[int, int], ...]:
    """Static, contiguous, exhaustive [a, b) row spans of the working
    set — the double-buffer granularity of the H2D stream."""
    t = max(1, min(int(tiles), capacity))
    edges = [round(capacity * i / t) for i in range(t + 1)]
    return tuple((a, b) for a, b in zip(edges[:-1], edges[1:]))


def make_host_round_fn(cfg, loss_fn, data, *, jit: bool = True, mesh=None,
                       client_axis: str = "clients", donate=None,
                       ctrl_arg: bool = False, arrivals_arg: bool = False,
                       spec: FlatSpec | None = None, ragged=None,
                       body_transform=None):
    """Build the streaming round: ``round_fn(HostState) -> (HostState,
    RoundMetrics)``, bit-identical to ``make_round_fn`` with the same
    config on the device backend.

    ``body_transform`` wraps the *solve* program (the per-round hot
    program) before jit — the analysis layer's mutation/retrace hook,
    mirroring its role on the device path.
    """
    _require(mesh is None, "is a single-host backend (mesh must be None "
             "— shard the device backend instead)")
    _require(not ctrl_arg and not arrivals_arg,
             "does not take ctrl/arrivals runtime args")
    _require(jit, "requires jit=True (the streaming legs wrap jitted "
             "device programs)")
    _require(spec is not None, "needs the flat (spec=) layout")
    _require(cfg.compact, "needs compact=True")
    n = cfg.n_clients
    dim = spec.dim
    compress = check_mode(cfg.consensus_compress)
    is_admm = cfg.algorithm in ADMM_FAMILY
    async_mode = cfg.max_staleness is not None
    fused = is_admm and _resolve_kernel_flag(cfg.fused_gss)
    if cfg.fused_gss and not fused:
        raise ValueError(
            "fused_gss=True needs compact=True, an ADMM-family "
            "algorithm and the flat (spec=) layout — got "
            f"compact={cfg.compact}, algorithm={cfg.algorithm!r}, "
            "flat=True")
    # ``fused`` is accepted but has nothing extra to fuse here: the
    # streaming round's solve already IS the one-pass gather→solve→
    # scatter dataflow over the (C, D) working set (the scatter happens
    # on the host), and fused ≡ unfused is bitwise on the device path.

    if ragged is not None:
        if ragged.n_clients != n:
            raise ValueError(f"ragged spec describes {ragged.n_clients} "
                             f"clients, cfg.n_clients={n}")
        assert data["x"].shape[0] == ragged.buffer_rows, \
            (data["x"].shape, ragged.buffer_rows)
        n_points = ragged.max_size
        masked = not ragged.uniform
    else:
        assert data["x"].shape[0] == n, (data["x"].shape, n)
        n_points = data["x"].shape[1]
        masked = False

    select = make_selection(cfg.selection_name(), rate=cfg.participation,
                            controller=_ctrl_cfg(cfg),
                            metric=cfg.trigger_metric)
    rho = cfg.local_rho()
    tree_solver = partial(_local_solve, loss_fn, rho=rho, lr=cfg.lr,
                          momentum=cfg.momentum)
    tree_masked_solver = partial(_masked_local_solve, loss_fn, rho=rho,
                                 lr=cfg.lr, momentum=cfg.momentum)

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
    c_min, capacity = capacity_bounds(n, cfg.participation,
                                      cfg.capacity_slack, cfg.capacity)
    adaptive = cfg.adaptive_capacity and cfg.capacity is None
    alpha = _ctrl_cfg(cfg).alpha
    rate_floor = cfg.participation * n
    spans = _tile_spans(capacity, getattr(cfg, "stream_tiles", 2))
    if donate is None:
        donate = jax.default_backend() != "cpu"

    # Round-static device residents: training data (gathered by slot
    # index inside the solve program, same op as the device block; an
    # argument of that program, not a constant) and the CSR index
    # columns.
    operands = RoundOperands({"x": jnp.asarray(data["x"]),
                              "y": jnp.asarray(data["y"])})
    if ragged is not None:
        offsets_dev = ragged.offsets_array()
        sizes_dev = ragged.sizes_array()

    # ------------------------------------------------------------------
    # program 1: plan — full-N vector work, no (N, D) operand anywhere
    # ------------------------------------------------------------------
    def _plan(rng, round_, ctrl, age, qload, distances, delay, ttl, hist):
        rng, sel_rng, data_rng = jax.random.split(rng, 3)
        view = _PlanView(ctrl=ctrl, round=round_)
        if async_mode:
            eligible = ttl == 0
            events = select.decide(sel_rng, view, distances, None,
                                   eligible=eligible) & eligible
        else:
            eligible = jnp.ones((n,), bool)
            events = select.decide(sel_rng, view, distances, None)
        limit = (adaptive_limit(qload, c_min, capacity)
                 if adaptive else None)
        plan = compact_plan(events, distances, capacity, age=age,
                            limit=limit, eligible=eligible)
        queue = queue_update(DeferQueue(age=age, load=qload), plan,
                             alpha=alpha)
        keys = jax.random.split(data_rng, n)
        out = dict(rng=rng, events=events, idx=plan.idx, valid=plan.valid,
                   age=queue.age, load=queue.load, limit=plan.limit,
                   keys_rows=keys[plan.idx],
                   num_events=jnp.sum(events.astype(jnp.int32)),
                   num_deferred=jnp.sum(
                       (queue.age > 0).astype(jnp.int32)))
        if async_mode:
            land, direct, defer, new_ttl = staleness_masks(
                plan.committed, delay, ttl)
            hist2 = record_issue(hist, events, round_)
            measured = measured_commits(hist2, delay, round_)
            ctrl2 = select.measure(ctrl, measured, None,
                                   staleness_delay=delay)
            out.update(ctrl=ctrl2, land=land, ttl=new_ttl, hist=hist2,
                       committed=direct | land,
                       num_inflight=jnp.sum(
                           (new_ttl > 0).astype(jnp.int32)),
                       num_landed=jnp.sum(land.astype(jnp.int32)))
        else:
            out.update(ctrl=select.measure(ctrl, events, None),
                       committed=plan.committed,
                       num_inflight=jnp.zeros((), jnp.int32),
                       num_landed=jnp.zeros((), jnp.int32))
        out["num_committed"] = jnp.sum(
            out["committed"].astype(jnp.int32))
        out["realized_slack"] = (plan.limit.astype(jnp.float32)
                                 / (rate_floor if rate_floor > 0 else 1.0))
        return out

    # ------------------------------------------------------------------
    # program 2: solve — width-C working set, the per-round hot program
    # ------------------------------------------------------------------
    def _cat(tiles):
        return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, 0)

    def _solve(omega, idx, keys_rows, th_tiles, lam_tiles):
        # Exact bit mirror of make_compact_block's post-plan sequence:
        # the tiles concatenate back to the same (C, D) rows the device
        # block gathers, and every op below matches it at width C.
        th_rows, lam_rows = _cat(th_tiles), _cat(lam_tiles)
        if is_admm:
            lam_new_rows = dual_ascent(lam_rows, th_rows, omega)
            center_rows = prox_center(omega, lam_new_rows)
        else:
            lam_new_rows = lam_rows  # stays zero
            center_rows = jnp.broadcast_to(omega[None], (capacity, dim))
        theta0_rows = (jnp.broadcast_to(omega[None], (capacity, dim))
                       if cfg.warm_start else th_rows)
        idx_b = jax.vmap(epoch_fn)(keys_rows)
        if ragged is None:
            x_slots, y_slots = operands["x"][idx], operands["y"][idx]
            th_out, losses = jax.vmap(solver)(
                theta0_rows, center_rows, x_slots, y_slots, idx_b)
        else:
            off_rows = offsets_dev[idx]
            size_rows = sizes_dev[idx]
            block_len = ragged.max_size

            def slice_rows(buf):
                return jax.vmap(
                    lambda o: jax.lax.dynamic_slice_in_dim(
                        buf, o, block_len, 0))(off_rows)

            x_rows, y_rows = (slice_rows(operands["x"]),
                              slice_rows(operands["y"]))
            if masked:
                th_out, losses = jax.vmap(masked_solver)(
                    theta0_rows, center_rows, x_rows, y_rows,
                    jnp.zeros_like(off_rows), size_rows, idx_b)
            else:
                th_out, losses = jax.vmap(solver)(
                    theta0_rows, center_rows, x_rows, y_rows, idx_b)
        z_rows = th_out + lam_new_rows if is_admm else th_out
        return th_out, lam_new_rows, z_rows, losses

    if body_transform is not None:
        _solve = body_transform(_solve)

    # ------------------------------------------------------------------
    # program 3: aggregate — the one full-width server pass (consensus
    # + next round's trigger distances over the same z rows)
    # ------------------------------------------------------------------
    def _aggregate(z_full, omega, comm, committed, num_committed,
                   losses, valid):
        if is_admm:
            if compress != "none":
                omega2, comm2 = ef_consensus(z_full, omega, comm,
                                             mode=compress,
                                             block=cfg.compress_block)
            else:
                omega2, comm2 = consensus_mean(z_full), comm
        else:
            if compress != "none":
                omega2, comm2 = ef_participant_mean(
                    z_full, committed, omega, comm, num_committed,
                    mode=compress, block=cfg.compress_block)
            else:
                omega2 = participant_mean(z_full, committed, omega,
                                          num_events=num_committed)
                comm2 = comm
        dists = trigger_distances(omega2, z_full, cfg.trigger_metric)
        return omega2, comm2, dists, participant_mean_loss(losses, valid)

    plan_step = jax.jit(_plan)
    solve_step = operands.jit(_solve,
                              donate_argnums=(3, 4) if donate else ())
    agg_step = (jax.jit(_aggregate, donate_argnums=(0,)) if donate
                else jax.jit(_aggregate))
    trig_step = jax.jit(partial(trigger_distances,
                                metric=cfg.trigger_metric))

    # Byte counters of the host glue; its phases are host spans
    # fedback/host.plan, host.h2d, host.solve, host.d2h, host.scatter
    # and host.agg (``repro.utils.spans``), on the profiler's clock.
    stats = {"rounds": 0, "h2d_row_bytes": 0, "d2h_row_bytes": 0,
             "h2d_full_bytes": 0, "d2h_full_bytes": 0,
             "d2h_plan_bytes": 0}
    _delay_np: list = []  # static per-client delays, fetched once

    def _put_tiles(rows: np.ndarray):
        # Dispatch every tile's H2D back-to-back (double-buffered
        # stream: the runtime overlaps copy t+1 with compute on t).
        with span("host.h2d"):
            tiles = tuple(jax.device_put(rows[a:b]) for a, b in spans)
        stats["h2d_row_bytes"] += rows.nbytes
        return tiles

    def round_fn(state: HostState):
        if state.distances is None:
            # Fresh init / just restored: one trigger pass seeds the
            # pipelined distance cache (afterwards the aggregate pass
            # maintains it for free).
            z_dev = jax.device_put(state.z_prev)
            stats["h2d_full_bytes"] += state.z_prev.nbytes
            state = HostState(**{**state.__dict__,
                                 "distances": trig_step(state.omega,
                                                        z_dev)})
        inflight = state.inflight
        with span("host.plan"):
            p = plan_step(state.rng, state.round, state.ctrl,
                          state.queue.age, state.queue.load,
                          state.distances,
                          None if inflight is None else inflight.delay,
                          None if inflight is None else inflight.ttl,
                          None if inflight is None else inflight.hist)
            np_idx = np.asarray(p["idx"])
            np_valid = np.asarray(p["valid"])
        stats["d2h_plan_bytes"] += np_idx.nbytes + np_valid.nbytes

        th_tiles = _put_tiles(state.theta[np_idx])
        lam_tiles = _put_tiles(state.lam[np_idx])
        with span("host.solve"):
            th_out, lam_new, z_rows, losses = solve_step(
                state.omega, p["idx"], p["keys_rows"], th_tiles, lam_tiles)
        with span("host.d2h"):
            np_th = np.asarray(th_out)
            np_lam = np.asarray(lam_new)
            np_z = np.asarray(z_rows)
        stats["d2h_row_bytes"] += np_th.nbytes + np_lam.nbytes + np_z.nbytes

        # --- host scatter: the valid slots' distinct client rows ------
        with span("host.scatter"):
            slot = np.flatnonzero(np_valid)
            cids = np_idx[slot]
            new_inflight = inflight
            if async_mode:
                if not _delay_np:
                    _delay_np.append(np.asarray(inflight.delay))
                np_land = np.asarray(p["land"])
                stats["d2h_plan_bytes"] += np_land.nbytes
                land_rows = np.flatnonzero(np_land)
                for buf, park in ((state.theta, inflight.theta),
                                  (state.lam, inflight.lam),
                                  (state.z_prev, inflight.z)):
                    buf[land_rows] = park[land_rows]
                d0 = _delay_np[0][cids] == 0
                for buf, park, rows in (
                        (state.theta, inflight.theta, np_th),
                        (state.lam, inflight.lam, np_lam),
                        (state.z_prev, inflight.z, np_z)):
                    buf[cids[d0]] = rows[slot[d0]]  # direct commits
                    park[cids[~d0]] = rows[slot[~d0]]  # deferred → park
                new_inflight = InFlight(delay=inflight.delay, ttl=p["ttl"],
                                        theta=inflight.theta,
                                        lam=inflight.lam, z=inflight.z,
                                        hist=p["hist"])
            else:
                state.theta[cids] = np_th[slot]
                state.z_prev[cids] = np_z[slot]
                if is_admm:
                    state.lam[cids] = np_lam[slot]

        # --- one full-width server pass -------------------------------
        with span("host.agg"):
            z_dev = jax.device_put(state.z_prev)
            stats["h2d_full_bytes"] += state.z_prev.nbytes
            comm_dev = None
            if compress != "none":
                comm_dev = jax.device_put(state.comm)
                stats["h2d_full_bytes"] += state.comm.nbytes
            omega2, comm2, dists, train_loss = agg_step(
                z_dev, state.omega, comm_dev, p["committed"],
                p["num_committed"], losses, p["valid"])
            comm_np = state.comm
            if compress != "none":
                comm_np = np.asarray(comm2)
                stats["d2h_full_bytes"] += comm_np.nbytes

        metrics = RoundMetrics(
            events=p["events"], num_events=p["num_events"],
            distances=state.distances, delta=p["ctrl"].delta,
            load=p["ctrl"].load, train_loss=train_loss,
            num_deferred=p["num_deferred"],
            realized_capacity=p["limit"],
            realized_slack=p["realized_slack"],
            num_inflight=p["num_inflight"], num_landed=p["num_landed"],
            committed=p["committed"])
        new_state = HostState(
            theta=state.theta, lam=state.lam, z_prev=state.z_prev,
            omega=omega2, ctrl=p["ctrl"], rng=p["rng"],
            round=state.round + 1,
            queue=DeferQueue(age=p["age"], load=p["load"]),
            distances=dists, inflight=new_inflight, comm=comm_np)
        stats["rounds"] += 1
        return new_state, metrics

    # --- metadata for the analysis layer and the benches --------------
    def solve_example_args():
        """Zero-valued operands matching the solve program's signature
        (the analysis layer traces/lowers ``solve_fn`` with these)."""
        th = tuple(jnp.zeros((b - a, dim), jnp.float32) for a, b in spans)
        lam = tuple(jnp.zeros((b - a, dim), jnp.float32)
                    for a, b in spans)
        return (jnp.zeros((dim,), jnp.float32),
                jnp.zeros((capacity,), jnp.int32),
                jnp.zeros((capacity, 2), jnp.uint32), th, lam)

    row_h2d = 2 * capacity * dim * 4  # θ, λ tiles up
    row_d2h = 3 * capacity * dim * 4  # θ_out, λ⁺, z rows down
    full_mult = 2 if compress != "none" else 1
    round_fn.planned_bytes = {
        "row_stream_h2d": row_h2d,
        "row_stream_d2h": row_d2h,
        "row_stream_budget": 8 * capacity * dim * 4,
        "server_pass_h2d": n * dim * 4 * full_mult,
        "server_pass_d2h": (n * dim * 4 if compress != "none" else 0),
        "plan_d2h": capacity * 5 + (n if async_mode else 0),
    }
    round_fn.stats = stats
    round_fn.solve_fn = _solve
    round_fn.solve_example_args = solve_example_args
    round_fn.solve_donate_argnums = (3, 4) if donate else ()
    round_fn.plan_step = plan_step
    round_fn.solve_step = solve_step
    round_fn.aggregate_step = agg_step
    round_fn.static_info = {
        "backend": "host", "capacity": capacity, "c_min": c_min,
        "adaptive": adaptive, "is_admm": is_admm,
        "ragged": ragged is not None, "masked": masked,
        "tiles": len(spans), "donate": donate, "fused": fused,
        "async": async_mode, "compress": compress,
    }
    return round_fn
