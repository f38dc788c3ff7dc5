"""What a per-layer metric's reader is given, and the loop over the
cell's per-layer metrics.  A reader is ``bench/metrics/<name>.py`` with
``read(ctx) -> float | None``; None leaves the metric out of the line.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Context:
    kind: str            # how the traffic runs: "rounds" or "serve"
    cfg: dict            # the configuration as run
    n_clients: int
    dim: int             # D, flat parameters of the client model
    capacity: int        # C, solve slots per round
    wall_s: float        # host wall time of the traced window
    steps: int           # rounds (ticks) the window ran
    committed: np.ndarray  # (steps, N) bool, rows committed per step
    sizes: np.ndarray    # (N,) examples per client
    flops_per_example: int  # forward FLOPs of one example
    peaks: dict          # bench/peaks.py row of this device
    trace: object        # trace_reduce.Reduced of the traced window


def context(cell, problem, device, out, trace) -> Context:
    from peaks import peaks_for
    from reference import capacity

    cfg = cell.cfg
    if problem.layout is None:
        sizes = np.full((cfg["n_clients"],),
                        problem.data["x"].shape[1], np.int64)
    else:
        sizes = np.asarray(problem.layout[0], np.int64)
    return Context(
        kind=cell.traffic["kind"], cfg=cfg, n_clients=cfg["n_clients"],
        dim=problem.spec.dim, capacity=capacity(cfg)[1],
        wall_s=out["wall_s"], steps=out.get("rounds", out.get("ticks")),
        committed=out["committed"], sizes=sizes,
        flops_per_example=cell.model.forward_flops(cfg),
        peaks=peaks_for(device.device_kind), trace=trace)


def per_layer(cell, problem, device, tracer, out):
    """(metrics, extra) of a ``--trace 1`` run: the cell's per-layer
    metrics, the device's busy and window seconds, the breakdown."""
    from harness import BenchError, metric_reader
    from trace_reduce import reduce_file

    path = tracer.xplane()
    if path is None:
        raise BenchError(f"no .xplane.pb under {tracer.dir}")
    trace = reduce_file(path)
    ctx = context(cell, problem, device, out, trace)
    from harness import KERNELS, say
    from trace_reduce import CUSTOM

    custom = sum(1 for op in trace.ops
                 if any(m in op.name or m in op.text for m in CUSTOM))
    say(phase="trace", steps=ctx.steps, custom_calls=custom,
        **{k: trace.kernel(k)[0] for k in KERNELS})
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    extra = {"device": {"busy_s": trace.busy_s, "window_s": trace.window_s},
             "breakdown": trace.breakdown()}
    return metrics, extra
