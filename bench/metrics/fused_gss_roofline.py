"""``fused_gss``' share of its HBM roofline, in %.

Bytes from the math of ``fused_gss_ref``: for each of the C slots it
reads the theta and lambda rows and the solved row (3 x D), for each
committed slot it writes the theta, lambda and z rows (3 x D), and it
reads omega once; fp32.  The committed slots are the mean over the
window's rounds.  Time: the kernel's custom call alone, mean over its
calls in the trace.  Moves ``rounds_per_s``.
"""


def hbm_bytes(c: int, committed: float, d: int) -> float:
    return (3 * c * d + 3 * committed * d + d) * 4.0


def read(ctx):
    calls, seconds = ctx.trace.kernel("fused_gss")
    if calls == 0 or seconds <= 0 or ctx.steps == 0:
        return None
    committed = float(ctx.committed.sum()) / ctx.steps
    need = hbm_bytes(ctx.capacity, committed, ctx.dim) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * need / (seconds / calls)
