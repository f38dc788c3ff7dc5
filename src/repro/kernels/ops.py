"""Public kernel entry points.

Each op dispatches between the Pallas TPU kernel and the pure-jnp
reference.  ``interpret=None`` (the default everywhere) is resolved by
:func:`_default_interpret`: compiled kernels on a TPU backend, interpret
mode elsewhere (the kernel *body* runs on the CPU, validating the exact
TPU program).  Pass ``interpret=False`` to force compilation, e.g. when
compiling ahead of time for a described TPU from a CPU host.

``trigger_sq_norms_pytree`` is the integration point used by the
FedBack server: it flattens stacked client pytrees into the (N, D)
layout the kernel wants.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .admm_update import (
    admm_update as _admm_update,
    admm_update_hbm_bytes,  # noqa: F401  (re-export: traffic model)
    admm_update_sharded as _admm_update_sharded,
)
from .flash_attention import flash_attention as _flash_attention
from .fused_gss import (
    fused_gss as _fused_gss,
    fused_gss_hbm_bytes,  # noqa: F401  (re-export: traffic model)
    fused_gss_ref,  # noqa: F401  (re-export: bit-exact jnp form)
)
from .ssd_scan import ssd_scan as _ssd_scan
from .trigger_norms import (
    trigger_sq_norms as _trigger_sq_norms,
    trigger_sq_norms_sharded as _trigger_sq_norms_sharded,
)


def _default_interpret() -> bool:
    """The mode ``interpret=None`` means: compiled on TPU only."""
    return jax.default_backend() != "tpu"


def trigger_sq_norms(z_prev, omega, *, interpret: bool | None = None):
    return _trigger_sq_norms(z_prev, omega, interpret=interpret)


def admm_update(theta, lam, omega, *, interpret: bool | None = None,
                with_z: bool = True, mesh=None, axis: str = "clients"):
    """Fused λ⁺/z/center pass over flat (N, D) client state.

    ``with_z=False`` drops the z output (the flat round's pre-solve
    form).  With ``mesh`` the kernel runs under ``shard_map`` over the
    client mesh axis — one launch per device on its local rows.
    """
    if mesh is not None:
        return _admm_update_sharded(theta, lam, omega, mesh, axis=axis,
                                    interpret=interpret, with_z=with_z)
    return _admm_update(theta, lam, omega, interpret=interpret,
                        with_z=with_z)


def fused_gss(idx, valid, solved, omega, theta, lam, z_prev=None, *,
              interpret: bool | None = None, with_z: bool = True):
    """Fused gather→ADMM-commit→scatter over the compact plan's slots.

    One Pallas pass replaces the compact round's post-solve commit
    (row gathers for the dual algebra + z assembly + three drop-indexed
    scatters); outputs alias the (N, D) state inputs so the scatter is
    in place.  ``fused_gss_ref`` is the bit-identical jnp form.
    """
    return _fused_gss(idx, valid, solved, omega, theta, lam, z_prev,
                      interpret=interpret, with_z=with_z)


def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash_attention(q, k, v, causal=causal, window=window,
                            block_q=block_q, block_k=block_k,
                            interpret=interpret)


def ssd_scan(states, decays, *, interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ssd_scan(states, decays, interpret=interpret)


def trigger_sq_norms_pytree(z_prev_stacked, omega, *,
                            interpret: bool | None = None,
                            mesh=None, axis: str = "clients"):
    """Stacked-pytree front-end for the FedBack server trigger.

    z_prev_stacked: pytree with leading client axis N; omega: matching
    pytree.  Returns (N,) fp32 squared distances.  With ``mesh`` the
    kernel runs under ``shard_map`` over the client mesh axis — one
    launch per device on its local client rows (the axis size must
    divide N).
    """
    z_leaves = jax.tree.leaves(z_prev_stacked)
    w_leaves = jax.tree.leaves(omega)
    n = z_leaves[0].shape[0]
    if len(z_leaves) == 1 and z_leaves[0].ndim == 2:
        # Flat layout: the state already *is* the (N, D) operand — read
        # it in place instead of paying a concatenate copy per round.
        z2d = z_leaves[0].astype(jnp.float32)
        w1d = w_leaves[0].reshape(-1).astype(jnp.float32)
    else:
        z2d = jnp.concatenate(
            [x.reshape(n, -1).astype(jnp.float32) for x in z_leaves], axis=1)
        w1d = jnp.concatenate(
            [x.reshape(-1).astype(jnp.float32) for x in w_leaves])
    if mesh is not None:
        return _trigger_sq_norms_sharded(z2d, w1d, mesh, axis=axis,
                                         interpret=interpret)
    return trigger_sq_norms(z2d, w1d, interpret=interpret)


# re-export oracles for convenience
trigger_sq_norms_ref = ref.trigger_sq_norms_ref
admm_update_ref = ref.admm_update_ref
flash_attention_ref = ref.flash_attention_ref
ssd_scan_ref = ref.ssd_scan_ref
