"""Device time of ``fedback/solve`` per round of the training window, in
ms: the local solves of the capacity slots (the vmapped SGD scan).  The
union of the scope's op intervals inside the window over the steps.
Moves ``rounds_per_s``."""
from spans import device_ms


def read(ctx):
    if ctx.kind != "rounds":
        return None
    return device_ms(ctx, "solve")
