"""Pallas TPU kernel: fused ADMM client update (paper Eq. 2.3).

The dual update, the upload variable and the prox center are three
elementwise expressions over the same (N, D) operands:

    λ⁺ = λ + θ − ω ;   z = θ + λ⁺ ;   c = ω − λ⁺

Unfused, XLA emits three HBM passes over N·D elements; the kernel does
one read of (θ, λ, ω-tile) and one write per output — the round-level
client update becomes strictly bandwidth-bound at its floor (5 streams
instead of 9).  Blocks (8, 1024): VPU-aligned, fp32 accumulate-free
(pure elementwise), dtype-preserving.

The flat round engine uses the ``with_z=False`` form: it needs λ⁺ and
the prox center *before* the local solve, while z is assembled from the
post-solve θ (``z = θ_out + λ⁺`` fuses into the event-gated commit), so
dropping the z stream saves one N·D write (4 streams total).

``admm_update_sharded`` runs the same kernel under ``shard_map`` over a
1-D ``clients`` mesh axis: one launch per device on its local client
rows, ω replicated — no collective, bit-identical to single-device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def admm_update_hbm_bytes(rows: int, dim: int, *, with_z: bool = True,
                          dtype_bytes: int = 4) -> int:
    """Modeled HBM traffic of one fused pass over ``rows`` client rows.

    One read each of θ and λ, one (amortized) read of the ω tile, one
    write per output — 5 streams with z, 4 without.  ``rows`` is the
    lever: the compacted round engine feeds the kernel C = ⌈slack·L̄·N⌉
    gathered rows instead of N, so the modeled bytes (and the measured
    wall-clock) scale with the capacity, not the client count.
    """
    n_out = 3 if with_z else 2
    return dtype_bytes * ((2 + n_out) * rows * dim + dim)


def _kernel3(th_ref, la_ref, w_ref, lam_out, z_out, c_out):
    th = th_ref[...]
    la = la_ref[...]
    w = w_ref[...][None, :]
    lam_new = la + th - w
    lam_out[...] = lam_new
    z_out[...] = th + lam_new
    c_out[...] = w - lam_new


def _kernel2(th_ref, la_ref, w_ref, lam_out, c_out):
    th = th_ref[...]
    la = la_ref[...]
    w = w_ref[...][None, :]
    lam_new = la + th - w
    lam_out[...] = lam_new
    c_out[...] = w - lam_new


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret", "with_z"))
def admm_update(theta, lam, omega, *, block_n: int = 8, block_d: int = 1024,
                interpret: bool | None = None, with_z: bool = True):
    """theta/lam: (N, D); omega: (D,) → (λ⁺, z, center) each (N, D).

    With ``with_z=False`` the z stream is skipped and the result is
    (λ⁺, center) — the pre-solve half of the round's client update.
    ``interpret=None`` runs the compiled kernel on a TPU backend and
    interpret mode elsewhere (``ops._default_interpret``).
    """
    from .ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    n, d = theta.shape
    n_pad = -n % block_n
    d_pad = -d % block_d
    if n_pad or d_pad:
        pad2 = ((0, n_pad), (0, d_pad))
        theta = jnp.pad(theta, pad2)
        lam = jnp.pad(lam, pad2)
    if d_pad:
        omega = jnp.pad(omega, (0, d_pad))
    np_, dp = theta.shape

    shape = jax.ShapeDtypeStruct((np_, dp), theta.dtype)
    spec2 = pl.BlockSpec((block_n, block_d), lambda i, j: (i, j))
    n_out = 3 if with_z else 2
    outs = pl.pallas_call(
        _kernel3 if with_z else _kernel2,
        grid=(np_ // block_n, dp // block_d),
        in_specs=[spec2, spec2,
                  pl.BlockSpec((block_d,), lambda i, j: (j,))],
        out_specs=(spec2,) * n_out,
        out_shape=(shape,) * n_out,
        interpret=interpret,
    )(theta, lam, omega)
    return tuple(x[:n, :d] for x in outs)


def admm_update_sharded(theta, lam, omega, mesh, *, axis: str = "clients",
                        block_n: int = 8, block_d: int = 1024,
                        interpret: bool | None = None,
                        with_z: bool = True):
    """Client-sharded fused update: ``shard_map`` over the ``clients``
    mesh axis, one kernel launch per device on its local rows.

    theta/lam: (N, D) sharded over ``axis``; omega: (D,) replicated.
    Pure elementwise per client row — no collective is introduced.
    """
    from jax.sharding import PartitionSpec as P

    kernel = functools.partial(admm_update, block_n=block_n, block_d=block_d,
                               interpret=interpret, with_z=with_z)
    n_out = 3 if with_z else 2
    fn = jax.shard_map(kernel, mesh=mesh,
                       in_specs=(P(axis, None), P(axis, None), P(None)),
                       out_specs=(P(axis, None),) * n_out,
                       check_vma=False)
    return fn(theta, lam, omega)
