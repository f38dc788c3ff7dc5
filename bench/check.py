"""The comparison that decides ``correct``.

The program's first rounds (or ticks), run through the timed call from
the seed, against the reference's same rounds from the same seed.  Each
number is a gap, judged against its limit in ``bench/limits/<cell>.json``:

* ``events_diff``: trigger decisions that differ where the reference's
  distance lies farther than ``reference.EVENT_BAND`` from its
  threshold (inside it, rounding decides and the reference took the
  compared run's decision);
* ``committed_diff``: committed rows that differ (the plan's set);
* ``dist_gap``: worst |d_prog - d_ref| / max(d_ref, median d_ref);
* ``delta_gap``: the same for the controller thresholds after a round;
* ``loss_gap``: worst relative gap of the rounds' mean train loss;
* ``state_gap``: worst ||x_prog,i - x_ref,i|| / max(||x_ref,i - x0_i||,
  median over moved rows) over the rows of theta, lambda and z after
  the last compared round, and ||omega_prog - omega_ref|| /
  ||omega_ref - omega0|| (x0: the state both started from);
* ``ledger_faults`` (served cells): ticks of the measured window whose
  events, commits and queue break the admission ledger
  (:func:`ledger_faults`).
"""
from __future__ import annotations

import numpy as np

from reference import undecided


def _rel(a, b, floor):
    return np.abs(a - b) / np.maximum(np.abs(b), floor)


def _worst(*values) -> float:
    """The largest value; NaN if any is NaN (Python's ``max`` would
    drop a NaN and let a run that produced one pass)."""
    return float(np.max(np.asarray(values, np.float64)))


def round_gaps(prog: list, ref: list, delta0: float) -> dict:
    """Gaps of per-round metrics; ``prog``/``ref`` are lists of dicts of
    host arrays (events, distances, delta, committed, train_loss)."""
    d_all = np.concatenate([r["distances"] for r in ref])
    d_floor = max(float(np.median(d_all)), 1e-30)
    dl_all = np.abs(np.concatenate([r["delta"] for r in ref]))
    dl_floor = max(float(np.median(dl_all)), 1e-30)
    events = committed = 0
    dist = delta = loss = 0.0
    used = None
    for p, r in zip(prog, ref, strict=True):
        threshold = (np.full_like(r["distances"], delta0) if used is None
                     else used)
        clear = ~undecided(r["distances"], threshold)
        events += int(np.sum((p["events"] != r["events"]) & clear))
        committed += int(np.sum(p["committed"] != r["committed"]))
        dist = _worst(dist, np.max(_rel(p["distances"], r["distances"],
                                        d_floor)))
        delta = _worst(delta, np.max(_rel(p["delta"], r["delta"],
                                          dl_floor)))
        loss = _worst(loss, _rel(np.float64(p["train_loss"]),
                                 np.float64(r["train_loss"]), 1e-30))
        used = r["delta"]
    return {"events_diff": events, "committed_diff": committed,
            "dist_gap": dist, "delta_gap": delta, "loss_gap": loss}


#: Rows of an (N, D) state cast to float64 at a time: the host holds
#: one block in float64, never a whole matrix, and a block that fits
#: the cache is also the fastest.
BLOCK_ROWS = 4


def row_norms(a: np.ndarray, b: np.ndarray | None = None) -> np.ndarray:
    """||a_i - b_i|| (``b`` None: ||a_i||) of every row, in float64, over
    blocks of ``BLOCK_ROWS`` rows.  Each row's norm is its own
    reduction, so it equals the norm taken over the whole matrix."""
    parts = []
    for lo in range(0, a.shape[0], BLOCK_ROWS):
        x = a[lo:lo + BLOCK_ROWS].astype(np.float64)
        if b is not None:
            x -= b[lo:lo + BLOCK_ROWS]
        parts.append(np.linalg.norm(x, axis=1))
    return np.concatenate(parts) if parts else np.empty(0, np.float64)


def row_gaps(prog: dict, ref: dict, start: dict) -> np.ndarray:
    """Each row's gap of theta/lambda/z against how far the reference
    moved that row (rows the reference left where they were: against
    the median move), over the rows the reference moved or the program
    did.  Two passes over blocks of rows: every row's move and the
    median from them, then the gaps."""
    gaps = []
    for key in ("theta", "lam", "z"):
        moved = row_norms(ref[key], start[key])
        scale = float(np.median(moved[moved > 0])) if np.any(moved > 0) \
            else 1e-30
        gap = row_norms(prog[key], ref[key]) / np.maximum(moved, scale)
        gaps.append(gap[(moved > 0) | (gap != 0)])
    return np.concatenate(gaps)


def state_gap(prog: dict, ref: dict, start: dict) -> float:
    """Worst row gap of theta/lambda/z (:func:`row_gaps`), and omega's
    gap against omega's move."""
    gaps = row_gaps(prog, ref, start)
    worst = _worst(0.0, np.max(gaps)) if gaps.size else 0.0
    om_p = prog["omega"].astype(np.float64)
    om_r = ref["omega"].astype(np.float64)
    moved = float(np.linalg.norm(om_r - start["omega"]))
    worst = _worst(worst, np.linalg.norm(om_p - om_r) / max(moved, 1e-30))
    return worst


def ledger_faults(arrivals: np.ndarray, events: np.ndarray,
                  committed: np.ndarray, deferred: np.ndarray,
                  pending0: np.ndarray, capacity: int) -> int:
    """Ticks at which the served engine breaks the admission ledger.

    Replays the window tick by tick from the queue it started with:
    an event needs an arrival; a commit needs demand (a fresh event or
    a pending admission); a tick commits at most ``capacity`` rows;
    what is left pending is the demand less the commits, and the
    engine's queue length (``num_deferred``) has to equal it.
    """
    pending = pending0.astype(bool).copy()
    faults = 0
    for t in range(arrivals.shape[0]):
        ev, cm = events[t].astype(bool), committed[t].astype(bool)
        demand = pending | ev
        bad = (bool(np.any(ev & ~arrivals[t]))
               or bool(np.any(cm & ~demand))
               or int(cm.sum()) > capacity)
        pending = demand & ~cm
        bad = bad or int(pending.sum()) != int(deferred[t])
        faults += int(bad)
    return faults


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers beside their limits.  A number
    without a limit, or one that is not finite, fails."""
    table = {}
    ok = True
    for name, value in numbers.items():
        limit = limits.get(name)
        table[name] = {"value": value, "limit": limit}
        if limit is None or not np.isfinite(value) or value > limit:
            ok = False
    return ok, table
