"""The serve loop's own work a tick: the mean of the length of each
``fedback/serve.tick`` span less its ``serve.fetch`` (upload, dispatch,
the scalar fetches and the admission ledger) in the window, in ms.
Moves ``commits_per_s``."""
from spans import in_window


def read(ctx):
    ticks = in_window(ctx, "serve.tick")
    fetches = in_window(ctx, "serve.fetch")
    if ctx.kind != "serve" or not ticks:
        return None
    return (sum(s.ms for s in ticks) - sum(s.ms for s in fetches)) \
        / len(ticks)
