"""Device time of ``fedback/plan`` per round of the training window, in ms:
selection, the controller, the compact plan, the queue update and the
row gathers.  The union of the scope's op intervals inside the window
over the steps.  Moves ``rounds_per_s``."""
from spans import device_ms


def read(ctx):
    if ctx.kind != "rounds":
        return None
    return device_ms(ctx, "plan")
