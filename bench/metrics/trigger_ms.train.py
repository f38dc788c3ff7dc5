"""Device time of ``fedback/trigger`` per round of the training window, in
ms: the trigger's distances (the ``trigger_sq_norms`` kernel and its
pad).  The union of the scope's op intervals inside the window over the
steps.  Moves ``rounds_per_s``."""
from spans import device_ms


def read(ctx):
    if ctx.kind != "rounds":
        return None
    return device_ms(ctx, "trigger")
