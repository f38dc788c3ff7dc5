"""Compressed consensus: quantized z-deltas with error feedback.

The round's one genuine collective is the consensus aggregation over
the client-stacked z rows (``engine.consensus_mean`` for the ADMM
family, ``engine.participant_mean`` for FedAvg/Prox).  At full fp32
width that moves 4 bytes per model coordinate per round through the
``clients`` mesh.  This module replaces the aggregation with an
**error-feedback** compressed form (``FLConfig.consensus_compress ∈
{"none", "bf16", "int8"}``) so the wire cost per round shrinks
alongside the round count FedBack already saves:

    δ_i  = z_i − ω_prev + e_i        z-delta with residual carry-in
    t_i  = Q(δ_i)                    level-1 per-client quantization
    e_i⁺ = δ_i − D(t_i)              client residual (FLState.comm)
    ω⁺   = ω_prev + (Σ_i D(t_i)) / denom   via the compressed wire

``ω_prev`` is the previous broadcast — already in ``FLState.omega`` —
so the reference costs no extra state.  The residual ``e_i`` is a
client-stacked (N, D) fp32 buffer (``FLState.comm``) that shards under
the clients mesh like the DeferQueue and threads through scan-of-vmap
sweeps and checkpoints as regular carry state.  Error feedback keeps
the scheme unbiased over time: whatever a round's quantizer drops is
replayed into the next round's delta, so the accumulated broadcast —
and with it the controller's trigger measurements ‖ω − z_i‖ — tracks
the uncompressed consensus instead of drifting (the composition
argument of *Optimal Client Sampling*, arXiv 2010.13723: compression
error lives in a feedback loop of its own and does not fight the
participation controller's integral action; cf. docs/compression.md).

**Two levels, one residual.**  Quantization happens twice: per client
(level 1: bf16 cast, or per-block symmetric int8 with fp32 scales) and
per mesh shard on the wire (level 2: each device's partial sum of
dequantized deltas is re-quantized so the cross-device collective
itself moves narrow bytes — an ``s8`` (D,) SUM all-reduce under a
shared per-block scale for int8, a ``u16``-bitcast all-gather of the
bf16 partials for bf16; naive bf16 ``psum`` would silently upcast the
collective to f32).  Level-2 wire error is shard-local and folded back
into the transmitting clients' residuals (1/m each), so a single
(N, D) residual buffer conserves every dropped bit:

    Σ_i e_i⁺  +  Σ transmitted  ==  Σ_i δ_i      (at every prefix)

**Layout/scope.**  Flat layout only (z as an (N, D) fp32 matrix) — the
engine's primary layout; ``make_round_fn`` rejects compression on the
stacked-pytree layout loudly.  ``consensus_compress="none"`` never
reaches this module: the round keeps the exact uncompressed
``consensus_mean``/``participant_mean`` calls and ``FLState.comm``
stays ``None``, so jaxprs and golden traces are bit-identical.

**Device-count semantics.**  The single-device path runs the same
two-level math with one shard (no collectives), so conservation and
error bounds are identical; exact bit-parity across device counts is
only promised for ``"none"`` (the int8 wire headroom ⌊127/n_shards⌋
and the bf16 partial-sum rounding depend on the shard count).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

#: Supported ``FLConfig.consensus_compress`` values.
MODES = ("none", "bf16", "int8")

#: Symmetric int8 code range; level-2 divides it by the shard count so
#: the s8 SUM all-reduce can never overflow.
INT8_CLIP = 127

#: Wire bytes per model coordinate by mode (the consensus payload term
#: of the CollectiveBudget rule and the roofline collective model).
WIRE_BYTES = {"none": 4, "bf16": 2, "int8": 1}


def check_mode(mode: str) -> str:
    if mode not in MODES:
        raise ValueError(
            f"consensus_compress must be one of {MODES}, got {mode!r}")
    return mode


def block_layout(dim: int, block: int) -> tuple[int, int]:
    """(n_blocks, block_size) of the per-block int8 scale layout.

    The block size is clamped to the vector length (a 16-coordinate toy
    problem must not pad to a 256-wide block), so ``n_blocks =
    ⌈D / min(block, D)⌉`` and padding is at most block−1 zeros.
    """
    b = max(1, min(int(block), int(dim)))
    return -(-int(dim) // b), b


def _blocked(x, block):
    """(..., D) → (..., nb, B) zero-padded block view."""
    d = x.shape[-1]
    nb, b = block_layout(d, block)
    pad = nb * b - d
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (nb, b))


def int8_quantize(x, *, block: int = 256, clip: int = INT8_CLIP):
    """Per-block symmetric int8 codes + fp32 scales.

    x: (..., D) fp32.  Returns ``(codes, scales)`` with codes int8 of
    shape (..., nb, B) (zero-padded past D) and scales fp32 (..., nb) =
    blockwise max|x| / clip.  An all-zero block quantizes to zero codes
    with scale 0 (dequantizes to exact zeros).
    """
    xb = _blocked(x, block)
    scale = jnp.max(jnp.abs(xb), axis=-1) / clip
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round(xb / safe[..., None]),
                     -clip, clip).astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def int8_dequantize(codes, scales, dim: int):
    """Inverse of :func:`int8_quantize`: (..., nb, B) codes → (..., D)."""
    xb = codes.astype(jnp.float32) * scales[..., None]
    return xb.reshape(xb.shape[:-2] + (-1,))[..., :dim]


def quantize_dequantize(x, mode: str, *, block: int = 256):
    """The level-1 transmit operator D(Q(x)): fp32 → fp32 through the
    wire dtype.  Round-trip error is 0 for ``none``, one bf16 ulp
    (≤ 2⁻⁸·|x|) for ``bf16`` and at most half a scale step
    (max|x_block| / (2·127)) per coordinate for ``int8``.
    """
    if mode == "none":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    codes, scales = int8_quantize(x, block=block)
    return int8_dequantize(codes, scales, x.shape[-1])


def _wire_int8(p, *, block, axis, n_shards):
    """Level-2 int8 wire: a genuine s8 (D,) SUM all-reduce.

    Every shard quantizes its fp32 partial sum ``p`` under a SHARED
    per-block scale (a tiny (nb,) fp32 MAX all-reduce of the blockwise
    |p| maxima), with codes clipped to ±⌊127/n_shards⌋ so the summed
    codes cannot overflow int8.  Returns ``(total, werr)``: the
    dequantized global sum (replicated) and this shard's local wire
    error ``p − sent``.
    """
    d = p.shape[-1]
    pb = _blocked(p, block)
    local_max = jnp.max(jnp.abs(pb), axis=-1)              # (nb,)
    gmax = lax.pmax(local_max, axis) if axis is not None else local_max
    clip = INT8_CLIP // n_shards
    scale = gmax / clip
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.clip(jnp.round(pb / safe[..., None]),
                     -clip, clip).astype(jnp.int8)
    sent = codes.astype(jnp.float32) * safe[..., None]
    werr = (pb - sent).reshape(-1)[:d]
    total_codes = lax.psum(codes, axis) if axis is not None else codes
    total = (total_codes.astype(jnp.float32)
             * safe[..., None]).reshape(-1)[:d]
    return total, werr


def _wire_bf16(p, *, axis):
    """Level-2 bf16 wire: a u16-bitcast all-gather of the partials.

    A bf16 ``psum`` (and a GSPMD bf16 sum) upcasts the collective to
    f32 on the wire; bitcasting the bf16 partial to u16 before
    ``all_gather`` keeps the collective at 2 bytes/coordinate, and the
    f32 accumulation of the gathered shard partials happens locally.
    """
    sent16 = p.astype(jnp.bfloat16)
    sent = sent16.astype(jnp.float32)
    werr = p - sent
    if axis is None:
        return sent, werr
    u = lax.bitcast_convert_type(sent16, jnp.uint16)
    gathered = lax.all_gather(u, axis)                     # (n_shards, D)
    vals = lax.bitcast_convert_type(gathered, jnp.bfloat16)
    return jnp.sum(vals.astype(jnp.float32), axis=0), werr


def _ef_body(z, omega, resid, mask, denom, *, mode, block, axis,
             n_shards):
    """Shard-local EF aggregation (full arrays when ``axis`` is None).

    z: (n_loc, D) fp32 rows; omega: (D,) replicated broadcast; resid:
    (n_loc, D) client residuals; mask: (n_loc,) bool transmitters or
    None (= every row, the ADMM family); denom: the global divisor —
    a static float N for the consensus mean, the traced committed
    count for the participant mean (ω falls back to itself at 0).
    Returns ``(omega_new, resid_new)``.
    """
    delta = z - omega[None, :] + resid                     # carry-in
    d = quantize_dequantize(delta, mode, block=block)
    if mask is None:
        resid1 = delta - d
        m_loc = jnp.float32(z.shape[0])
    else:
        mz = mask[:, None]
        d = jnp.where(mz, d, 0.0)
        resid1 = jnp.where(mz, delta - d, resid)
        m_loc = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    p = jnp.sum(d, axis=0)                                 # shard partial
    if mode == "int8":
        total, werr = _wire_int8(p, block=block, axis=axis,
                                 n_shards=n_shards)
    elif mode == "bf16":
        total, werr = _wire_bf16(p, axis=axis)
    else:  # exact wire — the EF identity check path of the tests
        total = lax.psum(p, axis) if axis is not None else p
        werr = jnp.zeros_like(p)
    # Shard-local wire error folds back into the transmitting rows'
    # residuals (1/m each): one (N, D) buffer conserves both levels.
    # A shard with zero transmitters has p == 0 exactly, hence werr == 0.
    share = werr[None, :] / m_loc
    if mask is None:
        resid_new = resid1 + share
        omega_new = omega + total / denom
    else:
        resid_new = jnp.where(mask[:, None], resid1 + share, resid1)
        denom_f = jnp.maximum(denom.astype(jnp.float32), 1.0)
        omega_new = jnp.where(denom > 0, omega + total / denom_f, omega)
    return omega_new, resid_new


def _mapped(body, mesh, axis, *, with_mask):
    from jax.sharding import PartitionSpec as P

    c, r = P(axis), P()
    in_specs = (c, r, c, c, r) if with_mask else (c, r, c)
    # check_vma=False: psum/pmax/all_gather outputs are replicated by
    # construction but the static inference can't see through the
    # bitcast chain (same opt-out as the sharded ragged solve).
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=(r, c), check_vma=False)


def ef_consensus(z, omega, resid, *, mode: str, block: int = 256,
                 mesh=None, axis: str = "clients"):
    """EF-compressed consensus mean (ADMM family, Eq. 2.4):
    ω⁺ = ω + (1/N) Σ_i D(Q(z_i − ω + e_i)).  Exact quantizers (mode
    ``"none"``) recover ``consensus_mean(z)`` with e ≡ 0.

    Returns ``(omega_new, resid_new)``.
    """
    check_mode(mode)
    n = z.shape[0]
    if mesh is None:
        return _ef_body(z, omega, resid, None, float(n), mode=mode,
                        block=block, axis=None, n_shards=1)
    n_shards = mesh.shape[axis]
    body = partial(_ef_body, mask=None, denom=float(n), mode=mode,
                   block=block, axis=axis, n_shards=n_shards)
    return _mapped(lambda zz, ww, rr: body(zz, ww, rr), mesh, axis,
                   with_mask=False)(z, omega, resid)


def ef_participant_mean(z, committed, omega, resid, num_committed, *,
                        mode: str, block: int = 256, mesh=None,
                        axis: str = "clients"):
    """EF-compressed participant mean (FedAvg/Prox aggregation):
    ω⁺ = ω + (1/|committed|) Σ_{i∈committed} D(Q(z_i − ω + e_i)), with
    ω unchanged (and nothing transmitted) when no client committed.
    Non-committed rows keep their residuals untouched.

    Returns ``(omega_new, resid_new)``.
    """
    check_mode(mode)
    if mesh is None:
        return _ef_body(z, omega, resid, committed, num_committed,
                        mode=mode, block=block, axis=None, n_shards=1)
    n_shards = mesh.shape[axis]
    body = partial(_ef_body, mode=mode, block=block, axis=axis,
                   n_shards=n_shards)
    return _mapped(body, mesh, axis, with_mask=True)(
        z, omega, resid, committed, num_committed)


def init_residual(n_clients: int, dim: int):
    """Zero-initialized client EF residual (``FLState.comm``)."""
    return jnp.zeros((n_clients, dim), jnp.float32)


def consensus_wire_bytes(dim: int, *, mode: str = "none",
                         block: int = 256,
                         world_size: int = 1) -> dict:
    """Modeled per-device link bytes of one consensus aggregation.

    Ring model (matching ``utils.hlo.collective_inventory``): an
    all-reduce moves 2·bytes·(n−1)/n per device, an all-gather moves
    output_bytes·(n−1)/n.  ``payload`` is the z-term — the number the
    never-increase gate and the ≤ 0.3× int8 acceptance ratio read —
    and ``overhead`` the int8 shared-scale MAX all-reduce.  ``uplink``
    is the client→server story (bytes one client's transmit occupies),
    which compresses on a single device too.
    """
    check_mode(mode)
    w = WIRE_BYTES[mode]
    nb, _ = block_layout(dim, block)
    frac = (world_size - 1) / world_size if world_size > 1 else 0.0
    if mode == "bf16":
        payload = world_size * dim * 2 * frac              # u16 all-gather
    else:
        payload = 2.0 * dim * w * frac                     # ring all-reduce
    overhead = 2.0 * nb * 4 * frac if mode == "int8" else 0.0
    uplink = dim * w + (nb * 4 if mode == "int8" else 0)
    return {
        "payload_link_bytes": payload,
        "overhead_link_bytes": overhead,
        "total_link_bytes": payload + overhead,
        "uplink_bytes_per_client": uplink,
    }
