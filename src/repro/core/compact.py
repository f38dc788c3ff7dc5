"""Capacity-bounded compaction: lossless, self-tuning solver dispatch.

The dense round engine runs the local solver for all N clients and
throws away the non-participants' work behind an event mask — exact
event accounting, but O(N) local-solve FLOPs per round regardless of
the controller's target rate L̄.  This module is the MoE-style dispatch
that makes round *compute* follow round *participation*:

    1. **plan**    — rank this round's *demand* (fresh trigger events ∪
       the deferral queue carried from earlier rounds) and assign the
       top slots up to the round's capacity limit; the rest stays in
       the queue (``DeferQueue``, part of ``FLState``).
    2. **gather**  — pull the planned clients' rows (θ, λ, data shard,
       PRNG key) into contiguous (C, ...) buffers — the solver and the
       fused ADMM kernel touch only C rows of state *and* data.
    3. **solve**   — run the vmapped scanned SGD prox solver over C rows
       instead of N.
    4. **scatter** — write committed rows back into the (N, ...) state;
       invalid slots (limit exceeds demand) drop out via an
       out-of-bounds scatter index.

**Deferral queue (lossless carry).**  A client that fired but missed a
slot is not dropped: it enters the queue (``age = 1``) and is carried
into every subsequent plan until served, with age-ordered priority —
a client deferred k rounds outranks every fresh event and every client
deferred < k rounds, so the plan serves the queue oldest-first and no
client can starve: with per-round limit C ≥ 1 a deferred client is
served within ⌈P/C⌉ rounds where P is the queue length when it joined
(later arrivals are strictly younger and never overtake it).  No unit
of work is lost or duplicated across rounds:

    demand_k  = events_k ∪ pending_k
    served_k  = top-C_k of demand_k          (committed)
    pending_{k+1} = demand_k \\ served_k      (ages += 1)

(a pending client whose trigger re-fires merges into its existing queue
entry — the carry is a state sync, idempotent by construction).

**Adaptive capacity.**  The static buffer size is C_max = ⌈slack·L̄·N⌉
(XLA shapes cannot change per round), but the per-round *commit limit*
C_k adapts to the controller's own load estimate: each client keeps an
EMA of its demand membership (``DeferQueue.load``, the Eq. 3.4 filter
applied to fired ∪ pending), and

    C_k = clip(⌈Σ_shard load⌉, ⌈L̄·n_shard⌉, C_max_shard)

so ``slack`` is a *bound*, not a constant — under light load the round
commits near the L̄·N floor, under bursts it opens up to the slack
ceiling.  The realized limit is surfaced per round as
``RoundMetrics.realized_capacity`` / ``realized_slack``.  C_k models
the *served-row budget* of a deployed server (upload/participation
bandwidth, the quantity FedBack's Θ(L̄·N) claim is about); the
simulator itself still executes all C_max slots every round — static
XLA shapes — so the benchmark HBM model is deliberately parameterized
by the static C, never by C_k.

Under a ``clients`` device mesh the block runs per-device via
``shard_map`` with per-shard budgets that round *up* (the global sum of
per-shard capacities always covers the global budget — see
:func:`capacity_for`).  Gather/solve/scatter and the queue itself never
cross devices — a deferred client is always served by the device owning
its state row (no-cross-shard-migration invariant) — so the only
collective in the round remains the consensus mean.  With
``capacity ≥ N`` no client is ever deferred and the compacted round
reproduces the dense path (bit-identical events, fp32-tolerance state)
— see tests/test_compact.py and tests/test_compact_properties.py.
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.utils.pytree import tree_broadcast_like

from .controller import demand_load_step
from .state import DeferQueue


class CompactPlan(NamedTuple):
    idx: jax.Array  # (C,) int32 — client row feeding each capacity slot
    valid: jax.Array  # (C,) bool — slot carries a genuine demand client
    committed: jax.Array  # (N,) bool — in demand AND within the limit
    num_deferred: jax.Array  # () int32 — demand beyond the limit (queue
    #                          length after this round)
    demand: jax.Array  # (N,) bool — fresh events ∪ carried deferrals
    num_demand: jax.Array  # () int32
    limit: jax.Array  # () int32 — rows this plan may commit (C_k ≤ C)


def init_queue(n_clients: int) -> DeferQueue:
    """Empty queue; load starts at 1 because δ⁰ = 0 makes every client
    fire in round 0 (paper Alg. 2) — the estimate predicts that burst,
    so the adaptive limit opens to the slack ceiling immediately."""
    return DeferQueue(age=jnp.zeros((n_clients,), jnp.int32),
                      load=jnp.ones((n_clients,), jnp.float32))


def capacity_for(n_clients: int, rate: float, slack: float,
                 capacity: int | None = None, *, n_shards: int = 1) -> int:
    """Static per-shard slot count C.

    ``capacity`` (if given) is the *global* solver-row budget; otherwise
    C_global = ⌈slack·L̄·N⌉.  The per-shard budget rounds **up**
    (⌈C_global/n_shards⌉) so the global sum of per-shard capacities
    never loses remainder clients when C_global is not divisible by the
    shard count; it is then clamped to [1, local client count] (a shard
    cannot commit more rows than it owns).
    """
    total = capacity if capacity is not None else math.ceil(
        slack * rate * n_clients)
    if n_clients % n_shards:
        raise ValueError(
            f"n_clients={n_clients} must be divisible by n_shards="
            f"{n_shards} (equal-size client shards)")
    n_local = n_clients // n_shards
    per_shard = max(1, min(math.ceil(total / n_shards), n_local))
    # Rounding up guarantees the global budget is covered (up to the
    # hard N ceiling — no plan can commit more rows than exist).
    assert per_shard * n_shards >= min(total, n_clients), \
        (per_shard, n_shards, total, n_clients)
    return per_shard


def capacity_bounds(n_clients: int, rate: float, slack: float,
                    capacity: int | None = None, *,
                    n_shards: int = 1) -> tuple[int, int]:
    """(C_min, C_max) per shard for the adaptive limit.

    C_max is :func:`capacity_for` (the static slot count); C_min is the
    participation floor ⌈L̄·n_local⌉ — the adaptive limit may never
    throttle below the controller's own target throughput.
    """
    c_max = capacity_for(n_clients, rate, slack, capacity,
                         n_shards=n_shards)
    n_local = n_clients // n_shards
    c_min = max(1, min(math.ceil(rate * n_local), c_max))
    return c_min, c_max


def adaptive_limit(qload: jax.Array, c_min: int, c_max: int) -> jax.Array:
    """Per-round commit limit C_k from the shard's demand-load estimate.

    qload: (n_local,) fp32 per-client demand EMAs; their sum estimates
    this shard's expected solver rows per round.  Returns a traced ()
    int32 in [c_min, c_max] — the *buffers* stay C_max-sized (static
    shapes), only the commit mask tightens.
    """
    est = jnp.ceil(jnp.sum(qload)).astype(jnp.int32)
    return jnp.clip(est, c_min, c_max)


def compact_plan(events: jax.Array, priority: jax.Array, capacity: int,
                 *, age: jax.Array | None = None,
                 limit: jax.Array | int | None = None,
                 eligible: jax.Array | None = None) -> CompactPlan:
    """Assign demand (events ∪ queue) to capacity slots.

    events: (N,) bool; priority: (N,) fp32 (trigger distances — larger
    means more urgent); age: (N,) int32 deferral ages (None ⇒ no queue).
    Ordering is lexicographic — demand first, then age descending
    (starvation-freedom: a client deferred k rounds outranks any fresh
    event and any younger deferral), then priority descending, then
    client index ascending — fully deterministic, so the plan is
    reproducible and vmap/shard_map friendly.

    ``limit`` (traced or static, ≤ capacity) caps how many slots may
    commit this round (adaptive capacity); the slot *buffers* stay
    ``capacity``-sized.

    ``eligible`` (None ⇒ everyone) masks clients out of the demand set
    entirely — the stale-tolerant engine passes ``ttl == 0`` so a
    client with an in-flight solve can neither re-fire nor be planned
    again until its payload lands (one outstanding solve per client).
    A queued client is always eligible by construction (it has not been
    serviced, so nothing of it is in flight); the mask enforces that
    invariant against the plan rather than assuming it.
    """
    n = events.shape[0]
    if age is None:
        age = jnp.zeros((n,), jnp.int32)
    demand = events | (age > 0)
    if eligible is not None:
        demand = demand & eligible
    # jnp.lexsort: last key is primary; ascending.  Index as the least-
    # significant key forces the low-index tie-break on every backend.
    order = jnp.lexsort((jnp.arange(n, dtype=jnp.int32),
                         -priority.astype(jnp.float32),
                         -age, ~demand)).astype(jnp.int32)
    idx = order[:capacity]
    num_demand = jnp.sum(demand.astype(jnp.int32))
    lim = jnp.minimum(jnp.asarray(capacity if limit is None else limit,
                                  jnp.int32), capacity)
    valid = jnp.arange(capacity, dtype=jnp.int32) < jnp.minimum(num_demand,
                                                                lim)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    committed = demand & (rank < lim)
    return CompactPlan(
        idx=idx, valid=valid, committed=committed,
        num_deferred=jnp.maximum(num_demand - lim, 0),
        demand=demand, num_demand=num_demand, limit=lim)


def queue_update(queue: DeferQueue, plan: CompactPlan, *,
                 alpha: float) -> DeferQueue:
    """Advance the deferral queue one round.

    Served clients leave the queue (age → 0); unserved demand ages by
    one (fresh overflow enters at age 1).  The demand EMA is the
    controller low-pass (Eq. 3.4) applied to demand membership.
    """
    new_age = jnp.where(plan.demand & ~plan.committed, queue.age + 1, 0)
    return DeferQueue(age=new_age.astype(jnp.int32),
                      load=demand_load_step(queue.load, plan.demand, alpha))


def gather_rows(tree, idx):
    """Pull rows ``idx`` of every (N, ...) leaf into (C, ...) buffers."""
    return jax.tree.map(lambda x: x[idx], tree)


def scatter_rows(current, rows, idx, valid):
    """Write slot rows back into the (N, ...) state; invalid slots are
    routed to an out-of-bounds index and dropped by the scatter."""
    n = jax.tree.leaves(current)[0].shape[0]
    drop_idx = jnp.where(valid, idx, n)
    return jax.tree.map(
        lambda c, r: c.at[drop_idx].set(r.astype(c.dtype), mode="drop"),
        current, rows)


def make_compact_block(solver: Callable, epoch_fn: Callable, capacity: int,
                       *, is_admm: bool, warm_start: bool,
                       use_admm_kernel: bool = False,
                       c_min: int | None = None, adaptive: bool = False,
                       alpha: float = 0.9, ragged=None,
                       masked_solver: Callable | None = None,
                       fused: bool = False,
                       use_fused_kernel: bool = False) -> Callable:
    """Build the per-shard gather→solve→scatter block.

    solver(theta0, center, x, y, idx) -> (theta, mean_loss), vmapped
    over capacity slots; epoch_fn(key) -> (steps, batch) gather indices.
    With ``adaptive`` the per-round commit limit follows the queue's
    demand-load estimate within [c_min, capacity]; otherwise the limit
    is the full ``capacity``.  The block is a pure function of one
    shard's rows — the deferral queue included, so a deferred client is
    always served by its own shard — and the caller can run it directly
    (single device) or under ``shard_map`` (mesh).

    Returns block(events, distances, eligible, age, qload, theta, lam,
    z_prev, omega, x, y, keys) -> (theta', lam', z_prev', age', qload',
    committed, slot_losses, slot_valid, limit(1,)).  ``eligible`` is the
    stale-tolerant engine's in-flight mask (all-True on the synchronous
    engine); state outputs are *service proposals* — the synchronous
    caller uses them as the committed state directly, the async caller
    routes them through the delay pipeline (``engine.staleness_commit``).

    With ``ragged`` (a ``repro.utils.ragged.RaggedSpec``) the block
    takes two trailing inputs — per-client CSR ``offsets`` and
    ``sizes`` — and ``x``/``y`` are the *pooled* (Σnᵢ+pad, ...)
    buffers: each capacity slot slices its client's CSR block out of
    the pool (``dynamic_slice`` at the static ``max(nᵢ)`` length — the
    spec's padding guarantees the slice never clamps), so the solver
    still streams C rows of data, they just come from CSR slices
    instead of a rectangular gather.  A non-uniform spec routes through
    ``masked_solver`` (pad-to-max with masked loss); a uniform spec
    statically selects the unmasked ``solver`` and reproduces the
    rectangular block bit for bit.

    With ``fused`` (flat-layout ADMM only) the post-solve commit — z
    assembly plus the three scatters — runs as one fused
    gather→ADMM→scatter pass (``kernels.fused_gss``): the Pallas
    megakernel when ``use_fused_kernel``, its bit-identical jnp form
    otherwise.  The reference three-pass path stays the parity oracle.
    """
    masked = ragged is not None and not ragged.uniform
    if masked and masked_solver is None:
        raise ValueError("non-uniform ragged compaction needs masked_solver")
    if fused and not is_admm:
        raise ValueError("fused commit is the ADMM dual algebra — "
                         "non-ADMM compaction has no λ/z streams to fuse")

    def solve_slots(theta0_rows, center_rows, x, y, keys_rows,
                    off_rows, size_rows):
        idx_b = jax.vmap(epoch_fn)(keys_rows)
        if ragged is None:
            # x/y here are the slot-gathered (C, nᵢ, ...) rows.
            return jax.vmap(solver)(theta0_rows, center_rows, x, y, idx_b)
        # Materialize each slot's (max_size, ...) CSR block — a single
        # contiguous slice per slot, never crossing into another
        # client's valid indices (padding keeps the last slices in
        # bounds; sliced-in neighbor rows beyond a slot's ``size`` are
        # unreachable: local indices are clamped to size-1).
        block_len = ragged.max_size

        def slice_rows(buf):
            return jax.vmap(
                lambda o: jax.lax.dynamic_slice_in_dim(buf, o, block_len,
                                                       0))(off_rows)

        x_rows, y_rows = slice_rows(x), slice_rows(y)
        if masked:
            return jax.vmap(masked_solver)(
                theta0_rows, center_rows, x_rows, y_rows,
                jnp.zeros_like(off_rows), size_rows, idx_b)
        return jax.vmap(solver)(theta0_rows, center_rows, x_rows, y_rows,
                                idx_b)

    def block(events, distances, eligible, age, qload, theta, lam, z_prev,
              omega, x, y, keys, offsets=None, sizes=None):
        limit = (adaptive_limit(qload, c_min, capacity)
                 if adaptive else None)
        plan = compact_plan(events, distances, capacity, age=age,
                            limit=limit, eligible=eligible)
        queue = queue_update(DeferQueue(age=age, load=qload), plan,
                             alpha=alpha)
        th_rows = gather_rows(theta, plan.idx)
        lam_rows = gather_rows(lam, plan.idx)

        if is_admm:
            if use_admm_kernel and not fused:
                from repro.kernels import ops
                lam_new_rows, center_rows = ops.admm_update(
                    th_rows, lam_rows, omega, with_z=False)
            else:
                # The fused path re-derives λ⁺ inside the commit kernel
                # — the pre-solve pass stays jnp (the solver only needs
                # the center), so one round launches ONE state kernel.
                from repro.core.engine import dual_ascent, prox_center
                lam_new_rows = dual_ascent(lam_rows, th_rows, omega)
                center_rows = prox_center(omega, lam_new_rows)
        else:
            lam_new_rows = lam_rows  # stays zero
            center_rows = tree_broadcast_like(omega, capacity)

        theta0_rows = (tree_broadcast_like(omega, capacity) if warm_start
                       else th_rows)
        # Data and PRNG keys flow through the same capacity slots: the
        # vmapped solver streams C rows of x/y (C CSR slices of the
        # pooled buffer when ragged), not N.
        if ragged is None:
            x_slots, y_slots = gather_rows(x, plan.idx), \
                gather_rows(y, plan.idx)
            off_rows = size_rows = None
        else:
            x_slots, y_slots = x, y  # pooled; sliced inside the solver
            off_rows = gather_rows(offsets, plan.idx)
            size_rows = gather_rows(sizes, plan.idx)
        th_out_rows, losses = solve_slots(
            theta0_rows, center_rows, x_slots, y_slots,
            gather_rows(keys, plan.idx), off_rows, size_rows)
        if fused:
            # One pass over the state instead of three: the fused op
            # re-derives λ⁺ from the gathered θ/λ rows (bit-identical
            # _kernel3 op order — λ is unchanged since the pre-solve
            # pass), assembles z = θ_out + λ⁺ in VMEM, and scatters all
            # three outputs in place on their aliased input buffers.
            from repro.kernels import ops
            op = ops.fused_gss if use_fused_kernel else ops.fused_gss_ref
            theta_new, lam_new, z_new = op(
                plan.idx, plan.valid, th_out_rows, omega, theta, lam,
                z_prev, with_z=True)
        else:
            z_rows = (jax.tree.map(jnp.add, th_out_rows, lam_new_rows)
                      if is_admm else th_out_rows)
            theta_new = scatter_rows(theta, th_out_rows, plan.idx,
                                     plan.valid)
            z_new = scatter_rows(z_prev, z_rows, plan.idx, plan.valid)
            lam_new = (scatter_rows(lam, lam_new_rows, plan.idx,
                                    plan.valid) if is_admm else lam)
        return (theta_new, lam_new, z_new, queue.age, queue.load,
                plan.committed, losses, plan.valid,
                plan.limit.reshape((1,)))

    # Static plan facts for the analysis layer (repro.analysis): the
    # solve width and limit bounds the compiled program was built for.
    block.static_info = {"capacity": capacity, "c_min": c_min,
                         "adaptive": adaptive, "is_admm": is_admm,
                         "use_admm_kernel": use_admm_kernel,
                         "fused": fused,
                         "use_fused_kernel": use_fused_kernel,
                         "ragged": ragged is not None}
    return block


def shard_mapped_block(block: Callable, mesh, *, axis: str = "clients",
                       ragged: bool = False) -> Callable:
    """Run the compact block per-device over the client mesh axis.

    Every input except ω is client-stacked (the deferral queue
    included — deferred clients never migrate across shards); the
    per-device commit limits come back stacked (n_shards,) so the
    caller can sum them into the round's realized capacity.  With
    ``ragged`` the x/y inputs are the pooled CSR buffers and stay
    replicated, while the trailing per-client offsets/sizes shard with
    the state — the offsets are *global* rows of the replicated pool,
    so a shard's solves read exactly its own clients' slices and
    gather/solve/scatter still never cross devices.
    """
    from jax.sharding import PartitionSpec as P

    c, r = P(axis), P()
    data_spec = (r, r) if ragged else (c, c)
    extra = (c, c) if ragged else ()
    mapped = jax.shard_map(
        block, mesh=mesh,
        in_specs=(c, c, c, c, c, c, c, c, r) + data_spec + (c,) + extra,
        out_specs=(c, c, c, c, c, c, c, c, c),
        check_vma=False)
    info = getattr(block, "static_info", None)
    if info is not None:  # carried through for the analysis layer
        mapped.static_info = dict(info, n_shards=mesh.shape[axis])
    return mapped
