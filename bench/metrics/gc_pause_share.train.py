"""Time Python's collector ran inside the training window (``fedback/gc``
spans), as a share of the window, in %.  Moves ``rounds_per_s``."""
from spans import gc_share


def read(ctx):
    if ctx.kind != "rounds":
        return None
    return gc_share(ctx)
