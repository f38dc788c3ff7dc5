"""Share of the serve() window in which no op ran on the device, in %:
1 - busy / window over the ``window`` span around the one serve() call.
Moves ``commits_per_s``."""


def read(ctx):
    if ctx.kind != "serve" or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
