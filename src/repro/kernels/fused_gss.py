"""Pallas TPU megakernel: fused gather → ADMM commit → scatter.

The compacted round (``core/compact.py``) commits a solve by touching
the (N, D) client state three separate times: gather θ/λ rows for the
dual algebra, assemble z = θ_out + λ⁺, then three drop-indexed scatters
write θ/λ/z_prev back.  Each pass is a full HBM round-trip over the
touched rows, and XLA will not fuse a gather with a scatter across the
solve boundary.  This kernel collapses the post-solve commit into ONE
pass over a (D block × slot) grid.  The ``CompactPlan`` slot indices
and commit mask arrive as scalar-prefetch operands, sorted by state
row, so for capacity slot i the pipeline

    * fetches the 8-row θ/λ/z_prev tiles holding row idx[i] into VMEM
      (the BlockSpec index maps read the sorted slot indices),
    * recomputes λ⁺ = λ + θ − ω and z = θ_solved + λ⁺ in registers —
      the exact ``_kernel3``/``_kernel2`` expressions of
      ``kernels/admm_update.py``, same op order, bit-identical fp32 —
    * and writes row idx[i] of each output tile, for committed
      (``plan.valid``) lanes only, leaving the other 7 rows as fetched.
      ``input_output_aliases`` pins each state output onto its input
      buffer, so a tile no slot touches is never read or written.

The TPU moves state in (8, 128) tiles: a (1, block_d) row block, or a
single-row DMA out of a tiled HBM array, is refused by its lowering.
So the kernel reads and writes whole 8-row tiles, and plan slots are
sorted by row so that slots sharing a tile run back to back: the
pipeline neither refetches an input nor writes back an output while
its block index stays the same, so one tile collects all of its slots'
rows before it goes back to HBM once.  The first slot of each run
copies the fetched tile into the output buffer; later slots update
their own row of it.  Plan indices are distinct by construction (a
``jnp.lexsort`` permutation prefix), so no two slots write one row.

Solver HBM traffic: 7 streams over the C committed rows
(``fused_gss_hbm_bytes``) against the three-pass reference's ~10, at
the granularity of 8-row tiles — a tile shared by several slots moves
once, a tile holding one slot moves 8 rows for it.

``fused_gss_ref`` is the jnp three-pass form of the *same* expression
graph — gather, ``λ + θ − ω``, drop-indexed scatters — kept as the
bit-exact parity oracle and as the execution path on backends where
interpret-mode Pallas is slower than XLA fusion (CPU CI).

VMEM budget per grid step: the 3 state tiles in and 3 out, the solved
tile and the ω tile, each double-buffered — 14·8·block_d·4 B ≈ 460 KiB
at block_d=1024, far below the ~16 MiB VMEM ceiling.  The last block
of a D that is no multiple of block_d is partial (the pipeline masks
its out-of-range lanes), so the state is never padded or copied.

On CPU the kernel executes in interpret mode (the exact TPU program,
validated bit-for-bit against ``fused_gss_ref`` in
tests/test_fused_gss.py); on a TPU backend it compiles
(``interpret=None`` picks the mode, see ``ops._default_interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8


def fused_gss_hbm_bytes(rows: int, dim: int, *, with_z: bool = True,
                        presolve: bool = False,
                        dtype_bytes: int = 4) -> int:
    """Modeled HBM traffic of one fused commit over ``rows`` slots.

    Kernel streams: reads θ/λ (+ z_prev) gathered rows and the solved
    (C, D) buffer, one (amortized) ω tile, writes one row per output —
    7 streams with z, 5 without, each ``rows·dim`` elements.  With
    ``presolve=True`` the round-level pre-solve λ⁺/center pass (2 row
    reads + 1 center write + ω) is added, giving the full fused compact
    round's solver-state model: 10·rows·dim + 2·dim elements.  Compare
    ``admm_update_hbm_bytes`` + 3 separate scatter passes for the
    unfused reference.
    """
    n_stream = 7 if with_z else 5
    total = n_stream * rows * dim + dim
    if presolve:
        total += 3 * rows * dim + dim
    return dtype_bytes * total


def _commit(row_ref, src_ref, vm_ref, s_ref, w_ref, ins, outs):
    """One (D block, sorted slot) step: update row idx[i] of the output
    tiles, starting from the fetched tiles on the first slot of a run."""
    i = pl.program_id(1)
    row = row_ref[i]
    prev = row_ref[jnp.maximum(i - 1, 0)]
    first = (i == 0) | (row // SUBLANES != prev // SUBLANES)

    @pl.when(first)
    def _():
        for o_ref, x_ref in zip(outs, ins, strict=True):
            o_ref[...] = x_ref[...]

    k = row % SUBLANES
    # Bring this slot's solved row to the sublane its state row has.
    shift = (k - src_ref[i] % SUBLANES + SUBLANES) % SUBLANES
    solved = pltpu.roll(s_ref[...], shift, 0)
    th = ins[0][...]
    la = ins[1][...]
    w = w_ref[...]
    lam_new = la + th - w  # _kernel3 op order — bit-identical λ⁺
    sublane = jax.lax.broadcasted_iota(jnp.int32, th.shape, 0)
    sel = (sublane == k) & (vm_ref[i] != 0)
    outs[0][...] = jnp.where(sel, solved, outs[0][...])
    outs[1][...] = jnp.where(sel, lam_new, outs[1][...])
    if len(outs) == 3:
        outs[2][...] = jnp.where(sel, solved + lam_new, outs[2][...])


def _fused_gss3(row_ref, src_ref, vm_ref, s_ref, w_ref, th_ref, la_ref,
                z_ref, tho_ref, lao_ref, zo_ref):
    _commit(row_ref, src_ref, vm_ref, s_ref, w_ref,
            (th_ref, la_ref, z_ref), (tho_ref, lao_ref, zo_ref))


def _fused_gss2(row_ref, src_ref, vm_ref, s_ref, w_ref, th_ref, la_ref,
                tho_ref, lao_ref):
    _commit(row_ref, src_ref, vm_ref, s_ref, w_ref,
            (th_ref, la_ref), (tho_ref, lao_ref))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret",
                                             "with_z"))
def fused_gss(idx, valid, solved, omega, theta, lam, z_prev=None, *,
              block_d: int = 1024, interpret: bool | None = None,
              with_z: bool = True):
    """Fused commit: scatter ``solved`` + ADMM duals into (N, D) state.

    idx: (C,) int32 plan slot → state row (distinct rows); valid: (C,)
    bool commit mask; solved: (C, D) post-solve θ rows; omega: (D,);
    theta/lam/z_prev: (N, D) state.  Returns (θ', λ', z') — or
    (θ', λ') with ``with_z=False`` — where row idx[i] of each output
    holds the committed update when valid[i] and the untouched input
    row otherwise.

    Outputs alias the state inputs (``input_output_aliases``), so under
    a donating jit the scatter is a true in-place update with no (N, D)
    copy.
    """
    from .ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    if with_z and z_prev is None:
        raise ValueError("with_z=True needs z_prev")
    n, d = theta.shape
    c = idx.shape[0]
    width = min(block_d, d + (-d % LANES))
    order = jnp.argsort(idx).astype(jnp.int32)
    rows = idx.astype(jnp.int32)[order]
    vmask = valid.astype(jnp.int32)[order]

    tile = pl.BlockSpec((SUBLANES, width),
                        lambda j, i, row, src, vm: (row[i] // SUBLANES, j))
    n_out = 3 if with_z else 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(pl.cdiv(d, width), c),
        in_specs=[pl.BlockSpec((SUBLANES, width),
                               lambda j, i, row, src, vm:
                               (src[i] // SUBLANES, j)),
                  pl.BlockSpec((1, width), lambda j, i, *_: (0, j))]
        + [tile] * n_out,
        out_specs=[tile] * n_out,
    )
    operands = (rows, order, vmask, solved, omega.reshape(1, d), theta,
                lam)
    if with_z:
        operands += (z_prev,)
    outs = pl.pallas_call(
        _fused_gss3 if with_z else _fused_gss2,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, d), theta.dtype)] * n_out,
        # alias positions count the scalar-prefetch operands: state
        # inputs sit at 5/6/7 of (rows, order, vmask, solved, ω, θ,
        # λ[, z]).
        input_output_aliases={5 + k: k for k in range(n_out)},
        interpret=interpret,
    )(*operands)
    return tuple(outs)


def fused_gss_ref(idx, valid, solved, omega, theta, lam, z_prev=None, *,
                  with_z: bool = True):
    """jnp three-pass reference: the kernel's exact expression graph.

    Gathers θ/λ rows, recomputes λ⁺ with the ``_kernel3`` op order, and
    commits through drop-indexed scatters (invalid lanes route to an
    out-of-bounds row, same no-op semantics as the kernel's masked
    write-back).  Bit-identical to :func:`fused_gss` on every lane.
    """
    if with_z and z_prev is None:
        raise ValueError("with_z=True needs z_prev")
    n = theta.shape[0]
    th_rows = theta[idx]
    la_rows = lam[idx]
    lam_new = la_rows + th_rows - omega[None, :]
    drop = jnp.where(valid, idx, n)
    tho = theta.at[drop].set(solved.astype(theta.dtype), mode="drop")
    lao = lam.at[drop].set(lam_new.astype(lam.dtype), mode="drop")
    if not with_z:
        return tho, lao
    z_rows = solved + lam_new
    zo = z_prev.at[drop].set(z_rows.astype(z_prev.dtype), mode="drop")
    return tho, lao, zo
