"""Roofline-term extraction from a compiled dry-run artifact.

Hardware model: the per-chip peaks in :data:`PEAKS`, keyed by
``jax.Device.device_kind``; the analytic models below price a TPU v5e
(:data:`MODEL_KIND`) — 197 TFLOP/s bf16, 819 GB/s HBM, 50 GB/s per ICI
link.  A measurement on a chip looks its peaks up with :func:`peaks`,
which refuses a device kind the table does not hold.

``compiled.cost_analysis()`` returns **per-device** (post-SPMD) FLOPs
and bytes (validated empirically: a (8,64)×(64,128) matmul on a (2,4)
mesh reports 1/8 of the global FLOPs), so:

    compute term    = flops_per_device / PEAK_FLOPS
    memory term     = bytes_per_device / HBM_BW
    collective term = link_bytes_per_device / LINK_BW

with link bytes from the ring-multiplier inventory in utils/hlo.py
(HLO shapes are per-device too).  MODEL_FLOPS = 6·N_active·D (train) or
2·N_active·D (inference) per device, ratioed against HLO FLOPs to
expose remat/dispatch/mask waste.
"""
from __future__ import annotations

from typing import Any

from repro.utils.hlo import collective_inventory, total_collective_bytes

#: Per-chip peaks by ``device_kind``, each entry with its source.
PEAKS = {
    # TPU v5e.  Source: Google Cloud documentation, "TPU v5e" — 197
    # TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
    # of ICI per chip over 4 links (50 GB/s each).  ``pcie_bw`` is not
    # in that source: it is assumed (PCIe Gen4 x16 nominal) and only
    # feeds the host-backend stream model.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bw": 819e9,
        "ici_link_bw": 50e9,
        "pcie_bw": 32e9,
    },
}

#: The chip the analytic models price.
MODEL_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds
    raise rather than borrow another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            "an entry with its source to repro.launch.roofline.PEAKS"
        ) from None


PEAK_FLOPS = peaks(MODEL_KIND)["bf16_flops"]
HBM_BW = peaks(MODEL_KIND)["hbm_bw"]
LINK_BW = peaks(MODEL_KIND)["ici_link_bw"]  # single-link model
PCIE_BW = peaks(MODEL_KIND)["pcie_bw"]


def model_flops_per_device(cfg, *, mode: str, batch: int, seq: int,
                           n_chips: int, active_params: int,
                           local_steps: int = 1) -> float:
    """6·N·D (train: fwd+bwd) / 2·N·D (inference fwd) per device."""
    if mode == "train":
        tokens = batch * seq * local_steps
        factor = 6.0
    elif mode == "prefill":
        tokens = batch * seq
        factor = 2.0
    else:  # decode: one token per sequence
        tokens = batch * 1
        factor = 2.0
    return factor * active_params * tokens / n_chips


def roofline_terms(cost: dict[str, Any], hlo_text: str, *,
                   world_size: int) -> dict[str, Any]:
    flops = float(cost.get("flops", 0.0) or 0.0)
    bytes_hbm = float(cost.get("bytes accessed", 0.0) or 0.0)
    coll = total_collective_bytes(hlo_text, world_size=world_size)
    t_c = flops / PEAK_FLOPS
    t_m = bytes_hbm / HBM_BW
    t_x = coll / LINK_BW
    terms = {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x}
    dominant = max(terms, key=terms.get)
    return {
        **terms,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": bytes_hbm,
        "collective_bytes_per_device": coll,
        "dominant": dominant.replace("_s", ""),
        "bound_time_s": max(t_c, t_m, t_x),
        "collectives": collective_inventory(hlo_text,
                                            world_size=world_size),
    }


def fedback_round_hbm_bytes(n_clients: int, solver_rows: int, dim: int,
                            *, data_bytes_per_client: int = 0,
                            dtype_bytes: int = 4,
                            fused: bool = False) -> dict[str, int]:
    """Modeled per-round HBM traffic of the flat FedBack round engine.

    The server side is irreducibly O(N·D): one trigger read of z_prev,
    one consensus read, and one commit write per state field (θ, λ,
    z_prev).  Everything client-side flows through the capacity slots —
    ``solver_rows`` is N on the dense path and C = ⌈slack·L̄·N⌉ (the
    realized adaptive limit at most) on the compacted path:

    * the fused λ⁺/center pass (``kernels.admm_update``, with_z=False
      form: 2 reads + 2 writes per row),
    * the post-solve z = θ_out + λ⁺ assembly (2 reads + 1 write),
    * the gathered data shards (``data_bytes_per_client`` per row) —
      the solver streams C rows of x/y, not N.

    With ``fused=True`` (the fused gather→ADMM→scatter commit,
    ``kernels.fused_gss``) the solver-state term is the honest fused
    model instead: the pre-solve center pass plus ONE kernel pass that
    gathers θ/λ/z_prev rows, re-derives λ⁺ and z, and scatters in
    place (``fused_gss_hbm_bytes(..., presolve=True)``) — the separate
    z assembly and per-output scatter passes are gone.  The dense
    path's model is unchanged (dense rounds never gather or scatter,
    so the historical 4+3-stream formula is exact there).

    Returns the separate server/solver terms plus the total, so the
    benchmark can show the solver term scaling with C while the server
    term stays pinned at N.
    """
    server = (1 + 1 + 3) * n_clients * dim * dtype_bytes
    if fused:
        from repro.kernels.fused_gss import fused_gss_hbm_bytes
        solver_state = fused_gss_hbm_bytes(solver_rows, dim, with_z=True,
                                           presolve=True,
                                           dtype_bytes=dtype_bytes)
    else:
        from repro.kernels.admm_update import admm_update_hbm_bytes
        solver_state = (admm_update_hbm_bytes(solver_rows, dim,
                                              with_z=False,
                                              dtype_bytes=dtype_bytes)
                        + 3 * solver_rows * dim * dtype_bytes)
    solver_data = solver_rows * data_bytes_per_client
    return {
        "server_bytes": server,
        "solver_state_bytes": solver_state,
        "solver_data_bytes": solver_data,
        "solver_bytes": solver_state + solver_data,
        "total_bytes": server + solver_state + solver_data,
    }


def fedback_ragged_round_hbm_bytes(n_clients: int, solver_rows: int,
                                   dim: int, *, sizes,
                                   row_bytes: int,
                                   dtype_bytes: int = 4) -> dict[str, int]:
    """Ragged variant of :func:`fedback_round_hbm_bytes`.

    With heterogeneous shards the solver's data term is governed by the
    pooled row count Σnᵢ, not by nᵢ·N: the dense ragged round (solver
    rows = N) streams every client's CSR slice once — Σnᵢ·row_bytes —
    via per-batch gathers from the pool.  The compacted round
    (solver_rows < N) materializes one *static* ``max(nᵢ)``-length
    block slice per capacity slot (``core.compact.solve_slots``), so
    its honest data term is ``solver_rows · max(nᵢ) · row_bytes`` —
    rows sliced, not merely rows used; the two coincide for uniform
    sizes.  State terms are unchanged (state rows are (N, D) regardless
    of shard sizes).  ``sizes`` is the per-client row-count sequence
    (``RaggedSpec.sizes``); ``row_bytes`` the bytes of one data row
    (x and y together).
    """
    base = fedback_round_hbm_bytes(n_clients, solver_rows, dim,
                                   data_bytes_per_client=0,
                                   dtype_bytes=dtype_bytes)
    sizes = tuple(int(s) for s in sizes)
    total_rows = sum(sizes)
    if solver_rows >= n_clients:  # dense: every CSR slice, streamed once
        solver_data = total_rows * row_bytes
    else:  # compacted: static max-length block slice per slot
        solver_data = solver_rows * max(sizes) * row_bytes
    return {
        "server_bytes": base["server_bytes"],
        "solver_state_bytes": base["solver_state_bytes"],
        "solver_data_bytes": solver_data,
        "solver_bytes": base["solver_state_bytes"] + solver_data,
        "total_bytes": base["server_bytes"] + base["solver_state_bytes"]
        + solver_data,
        "data_rows_total": total_rows,
    }


def host_stream_bytes(n_clients: int, capacity: int, dim: int, *,
                      compress: str = "none",
                      data_bytes_per_client: int = 0,
                      dtype_bytes: int = 4) -> dict[str, float]:
    """Planned host<->device traffic of one host-backend round
    (``state_backend="host"``, ``core.hoststate``) plus the modeled
    stream/solve overlap of the double-buffered working set.

    The byte model mirrors ``make_host_round_fn``'s
    ``round_fn.planned_bytes`` exactly — the pair is what the
    ``host-transfer-budget`` tracecheck rule and the BENCH_round gate
    compare against measured transfer counters:

    * row stream up:    θ, λ gather tiles            → 2·C·D·b
    * row stream down:  θ', λ⁺, z working-set rows   → 3·C·D·b
    * budget:           8·C·D·b (headroom for a future z_prev/EF tile)
    * server pass up:   z_prev (plus the EF residual under
                        ``consensus_compress``)       → N·D·b·{1,2}
    * server pass down: the folded-back EF residual   → N·D·b·{0,1}

    Training data never crosses per round — it is round-static and
    stays device-resident, gathered by slot index inside the solve
    program (the same dataflow as the device backend's compact block).

    ``modeled_overlap_fraction`` is the share of the row stream a
    double-buffered schedule can hide behind the solve compute:
    min(t_solve, t_stream)/t_stream on the PCIe + HBM model.  The
    benchmark reports the measured fraction next to it (≈ 0 on CPU,
    where transfers are memcpys on the compute thread).
    """
    row_h2d = 2 * capacity * dim * dtype_bytes
    row_d2h = 3 * capacity * dim * dtype_bytes
    full_mult = 2 if compress != "none" else 1
    server_h2d = n_clients * dim * dtype_bytes * full_mult
    server_d2h = (n_clients * dim * dtype_bytes
                  if compress != "none" else 0)
    solver = fedback_round_hbm_bytes(
        n_clients, capacity, dim,
        data_bytes_per_client=data_bytes_per_client,
        dtype_bytes=dtype_bytes)
    t_stream = (row_h2d + row_d2h) / PCIE_BW
    t_solve = solver["solver_bytes"] / HBM_BW
    return {
        "row_stream_h2d_bytes": row_h2d,
        "row_stream_d2h_bytes": row_d2h,
        "row_stream_budget_bytes": 8 * capacity * dim * dtype_bytes,
        "server_pass_h2d_bytes": server_h2d,
        "server_pass_d2h_bytes": server_d2h,
        "device_working_set_bytes": 5 * capacity * dim * dtype_bytes,
        "stream_s": t_stream,
        "solve_s": t_solve,
        "modeled_overlap_fraction": (
            min(t_solve, t_stream) / max(t_stream, 1e-30)),
    }


def consensus_collective_s(dim: int, *, mode: str = "none",
                           block: int = 256,
                           world_size: int = 1) -> dict[str, float]:
    """Modeled wire time of one consensus aggregation under
    ``consensus_compress`` (the compressed collective term).

    Delegates the byte model to :func:`repro.core.compress.
    consensus_wire_bytes` — an fp32/int8 ring all-reduce at the wire
    dtype (int8 adds the (nb,) fp32 shared-scale MAX reduce as an
    overhead term), a u16 all-gather for bf16 — and prices it at
    ``LINK_BW``.  The returned dict carries the byte breakdown next to
    ``collective_s`` so BENCH_comm.json can gate bytes and the roofline
    can stack times from the same numbers.
    """
    from repro.core.compress import consensus_wire_bytes

    wire = consensus_wire_bytes(dim, mode=mode, block=block,
                                world_size=world_size)
    return {**wire, "collective_s": wire["total_link_bytes"] / LINK_BW}


def fedback_round_memory_s(n_clients: int, solver_rows: int, dim: int,
                           *, data_bytes_per_client: int = 0,
                           dtype_bytes: int = 4) -> float:
    """Memory roofline term (seconds) of one flat FedBack round."""
    return fedback_round_hbm_bytes(
        n_clients, solver_rows, dim,
        data_bytes_per_client=data_bytes_per_client,
        dtype_bytes=dtype_bytes)["total_bytes"] / HBM_BW


def fedback_async_overlap(n_clients: int, solver_rows: int, dim: int, *,
                          max_staleness: int, n_chips: int = 1,
                          data_bytes_per_client: int = 0,
                          dtype_bytes: int = 4,
                          compress: str = "none",
                          compress_block: int = 256) -> dict[str, float]:
    """Modeled round-time overlap of the stale-tolerant engine.

    The synchronous round's critical path is serial: the solver term
    (gathered state + data through the capacity slots) must finish
    before the server term (trigger read, consensus all-reduce, commit
    writes) can run.  With ``max_staleness ≥ 1`` the commit rule
    tolerates solves landing up to S rounds late, so the solver stream
    of round k overlaps the server/collective stream of rounds
    k..k+S−1 and the steady-state critical path is the *maximum* of the
    two terms, not their sum:

        t_sync  = t_solver + t_server (+ t_collective)
        t_async = max(t_solver, t_server + t_collective)

    The collective term models the consensus all-reduce over the
    ``clients`` mesh (ring all-reduce moves ~2·D bytes per chip);
    under ``compress`` it switches to the compressed wire model
    (:func:`consensus_collective_s`) — the uncompressed default keeps
    the historical conservative no-(n−1)/n-discount formula so
    committed BENCH_round baselines stay comparable.
    Returns both modeled times plus the overlap speedup — the number
    the async rows of BENCH_round.json carry next to the measured
    wall-clock, so the benchmark can show how much of the modeled
    overlap the XLA schedule actually realizes.
    """
    hbm = fedback_round_hbm_bytes(
        n_clients, solver_rows, dim,
        data_bytes_per_client=data_bytes_per_client,
        dtype_bytes=dtype_bytes)
    t_solver = hbm["solver_bytes"] / HBM_BW
    t_server = hbm["server_bytes"] / HBM_BW
    if n_chips <= 1:
        t_coll = 0.0
    elif compress == "none":
        t_coll = 2.0 * dim * dtype_bytes / LINK_BW
    else:
        t_coll = consensus_collective_s(
            dim, mode=compress, block=compress_block,
            world_size=n_chips)["collective_s"]
    t_sync = t_solver + t_server + t_coll
    t_async = (max(t_solver, t_server + t_coll) if max_staleness > 0
               else t_sync)
    return {
        "solver_s": t_solver,
        "server_s": t_server,
        "collective_s": t_coll,
        "modeled_sync_s": t_sync,
        "modeled_async_s": t_async,
        "modeled_overlap_speedup": t_sync / max(t_async, 1e-30),
    }


def summarize(record: dict) -> str:
    r = record
    t = r["roofline"]
    mfu = (r.get("model_flops_per_device", 0.0) /
           max(t["hlo_flops_per_device"], 1.0))
    return (f"{r['arch']:24s} {r['shape']:12s} mesh={r['mesh']:10s} "
            f"compute={t['compute_s']*1e3:9.3f}ms "
            f"memory={t['memory_s']*1e3:9.3f}ms "
            f"coll={t['collective_s']*1e3:9.3f}ms "
            f"dom={t['dominant']:10s} useful/hlo={mfu:5.2f}")
