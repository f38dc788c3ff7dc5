"""Arrival traces of the served traffic mixes, from their parameters.

A trace is a (ticks, N) bool mask: client i's update reaches the server
at tick t.  ``bursty`` is a quiet Bernoulli(rate * quiet_frac) baseline
with a ``burst_len``-tick burst at Bernoulli(burst_rate) every
``burst_every`` ticks; ``poisson`` is Bernoulli(rate) every tick (the
arithmetic of ``repro.core.schedule.make_trace``, kept here so that the
program cannot change the load it is measured under).

The mask is drawn once from the mix's ``trace_seed`` and its columns
are then permuted by the run's seed: every seed offers the same number
of arrivals at every tick, to different clients.
"""
from __future__ import annotations

import numpy as np

KINDS = ("poisson", "bursty")


def tick_rates(spec: dict, ticks: int) -> np.ndarray:
    kind = spec["kind"]
    if kind == "poisson":
        return np.full((ticks,), spec["rate"])
    if kind == "bursty":
        rates = np.full((ticks,), spec["rate"] * spec["quiet_frac"])
        every = max(spec["burst_every"], 1)
        for start in range(0, ticks, every):
            rates[start:start + spec["burst_len"]] = spec["burst_rate"]
        return rates
    raise ValueError(f"unknown arrival kind {kind!r}; expected {KINDS}")


def make_arrivals(spec: dict, n_clients: int, ticks: int,
                  seed: int) -> np.ndarray:
    rates = np.clip(tick_rates(spec, ticks), 0.0, 1.0)
    base = np.random.default_rng(spec["trace_seed"]).random(
        (ticks, n_clients)) < rates[:, None]
    perm = np.random.default_rng(seed).permutation(n_clients)
    return base[:, perm]
