"""Model FLOPs of the local solves that committed, per second of the
window, as a share of the chip's bf16 peak, in %.

Counts the work the algorithm needs, whatever runs it: each committed
client i does epochs x (n_i // batch) x batch example-steps, each of 3
forward passes' FLOPs (forward plus backward).  Padded steps (the
pooled layout scans max(n_i) virtual rows) and the empty slots of the C
buffer do not count.  The peak is the bf16 one: fp32 matmuls on the
TPU run as bf16 passes.  Moves ``rounds_per_s``.
"""
import numpy as np


def solve_flops(ctx) -> float:
    epochs, batch = ctx.cfg["epochs"], ctx.cfg["batch_size"]
    per_client = epochs * (ctx.sizes // batch) * batch * 3.0 \
        * ctx.flops_per_example
    return float(np.sum(ctx.committed.astype(np.float64)
                        * per_client[None, :]))


def read(ctx):
    if ctx.kind != "rounds" or ctx.wall_s <= 0:
        return None
    flops = solve_flops(ctx)
    if flops <= 0:
        return None
    return 100.0 * flops / ctx.wall_s / ctx.peaks["bf16_flops"]
