"""Share of the training window in which no op ran on the device, in %:
1 - busy / window over the ``window`` span of the trace (run_rounds
chunks back to back).  Moves ``rounds_per_s``."""


def read(ctx):
    if ctx.kind != "rounds" or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
