"""95th percentile over the window's ticks of the deferral queue's
length, in clients: the ``deferred`` argument of each
``fedback/serve.ledger`` span (``RoundMetrics.num_deferred``, which the
serve loop reads every tick).  Moves ``commit_latency_p95_ms``."""
import numpy as np

from spans import in_window


def read(ctx):
    ledgers = in_window(ctx, "serve.ledger")
    if ctx.kind != "serve" or not ledgers:
        return None
    return float(np.percentile([s.args["deferred"] for s in ledgers], 95))
