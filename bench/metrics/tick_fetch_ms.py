"""The serve loop's wait on the device: the mean length of the
``fedback/serve.fetch`` spans (the tick's events and commit mask
fetched, up to the commit stamp) in the window, in ms.  Moves
``commits_per_s``."""
import numpy as np

from spans import in_window


def read(ctx):
    fetches = in_window(ctx, "serve.fetch")
    if ctx.kind != "serve" or not fetches:
        return None
    return float(np.mean([s.ms for s in fetches]))
