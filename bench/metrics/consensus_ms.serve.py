"""Device time of ``fedback/consensus`` per tick of the serve() window, in
ms: the consensus mean and the round's metrics.  The union of the
scope's op intervals inside the window over the steps.  Moves
``commits_per_s``."""
from spans import device_ms


def read(ctx):
    if ctx.kind != "serve":
        return None
    return device_ms(ctx, "consensus")
