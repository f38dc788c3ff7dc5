"""The host's cost to dispatch one round: the mean length of the
``fedback/round`` spans (one ``round_fn(state)`` call inside
``run_rounds``) in the window, in ms.  Above the device's time a round,
the host sets the pace.  Moves ``rounds_per_s``."""
import numpy as np

from spans import in_window


def read(ctx):
    rounds = in_window(ctx, "round")
    if ctx.kind != "rounds" or not rounds:
        return None
    return float(np.mean([s.ms for s in rounds]))
