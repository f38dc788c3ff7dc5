"""``trigger_sq_norms``' share of its HBM roofline, in %.

Bytes from the math, not the tiling: read z_prev (N x D) and omega (D),
write one squared distance per client, fp32: (N D + D + N) x 4.  Time:
the kernel's custom call alone, mean over its calls in the trace; the
jnp pad copy before it is outside the kernel and shows only end to end.
Moves ``rounds_per_s``.
"""


def hbm_bytes(n: int, d: int) -> int:
    return (n * d + d + n) * 4


def read(ctx):
    calls, seconds = ctx.trace.kernel("trigger_sq_norms")
    if calls == 0 or seconds <= 0:
        return None
    need = hbm_bytes(ctx.n_clients, ctx.dim) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / (seconds / calls)
