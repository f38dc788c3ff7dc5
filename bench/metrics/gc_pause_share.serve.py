"""Time Python's collector ran inside the serve() window (``fedback/gc``
spans), as a share of the window, in %.  Moves ``commits_per_s``."""
from spans import gc_share


def read(ctx):
    if ctx.kind != "serve":
        return None
    return gc_share(ctx)
