"""Pallas TPU kernel: per-client trigger distances (FedBack's server
hot spot).

Computes r_i = ‖z_i^prev − ω‖² for all N clients in a single pass over
HBM.  Workload is pure bandwidth: N·D reads of z plus D reads of ω
(re-read per client block — ω stays VMEM-resident across the inner
grid dimension).

TPU adaptation (vs. a CUDA atomics reduction): the grid is
(client-blocks × param-blocks) with the param dimension innermost;
per-client partial sums live in an fp32 VMEM scratch that persists
across the sequential inner grid, so each client's accumulator never
round-trips to HBM.  Blocks are (8, 1024) — 8-row sublane alignment,
128-lane multiples — 32 KiB of VMEM per z tile in fp32.  The
accumulator and the output block are (8, 128): a rank-1 (8,) block is
below the TPU's lane tile, so each row's running sum is kept broadcast
across one 128-lane row and lane 0 is sliced out after the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _kernel(z_ref, w_ref, o_ref, acc_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    diff = z_ref[...].astype(jnp.float32) - w_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("block_n", "block_d",
                                             "interpret"))
def trigger_sq_norms(z_prev, omega, *, block_n: int = 8,
                     block_d: int = 1024, interpret: bool | None = None):
    """z_prev: (N, D), omega: (D,) → (N,) fp32 squared distances.

    Pads N and D to block multiples (ω pads with the same zeros as z, so
    padding contributes exactly 0 to every sum).  ``interpret=None``
    runs the compiled kernel on a TPU backend and interpret mode
    elsewhere (``ops._default_interpret``).
    """
    from .ops import _default_interpret

    if interpret is None:
        interpret = _default_interpret()
    n, d = z_prev.shape
    n_pad = -n % block_n
    d_pad = -d % block_d
    if n_pad or d_pad:
        z_prev = jnp.pad(z_prev, ((0, n_pad), (0, d_pad)))
    if d_pad:
        omega = jnp.pad(omega, (0, d_pad))
    np_, dp = z_prev.shape

    out = pl.pallas_call(
        _kernel,
        grid=(np_ // block_n, dp // block_d),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_d,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((block_n, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, LANES), jnp.float32)],
        interpret=interpret,
    )(z_prev, omega)
    return out[:n, 0]


def trigger_sq_norms_sharded(z_prev, omega, mesh, *, axis: str = "clients",
                             block_n: int = 8, block_d: int = 1024,
                             interpret: bool | None = None):
    """Client-sharded trigger norms: ``shard_map`` over the ``clients``
    mesh axis, one Pallas kernel launch per device on its local rows.

    z_prev: (N, D) sharded over ``axis`` (the axis size must divide N);
    omega: (D,) replicated.  The per-client reduction over D is device-
    local — the only collective in the FedBack round stays the consensus
    mean — so the result is bit-identical to the single-device kernel.
    """
    from jax.sharding import PartitionSpec as P

    kernel = functools.partial(trigger_sq_norms, block_n=block_n,
                               block_d=block_d, interpret=interpret)
    fn = jax.shard_map(kernel, mesh=mesh,
                       in_specs=(P(axis, None), P(None)), out_specs=P(axis),
                       check_vma=False)
    return fn(z_prev, omega)
