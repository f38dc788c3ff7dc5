"""The one reduction from a profiler trace (``.xplane.pb``) to the
intervals the per-layer metrics read.

* Device ops: the events of the first TPU plane's ``XLA Ops`` line.
  Busy time is the union of their intervals inside the window; idle
  gaps are the holes in that union.
* Kernels: a device op belongs to a kernel when it is a custom call
  and the kernel's name is in the op's name or in one of its string
  stats (a Pallas custom call carries ``jit(<kernel>)`` in its op
  metadata).  The jnp ops of the kernel's wrapper (a pad, a sort) carry
  the name too, but are no custom calls: they count only end to end.
* Host spans: the harness's ``jax.profiler.TraceAnnotation`` events on
  the host plane.  The span named ``window`` bounds the window; each
  idle gap is named by the host span inside the window that covers most
  of it.

Both planes are on the profiler's one clock (nanoseconds).
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict

OPS_LINE = "XLA Ops"
CUSTOM = ("custom-call", "custom_call")
WINDOW_SPAN = "window"
HOST_SPANS = ("window", "run_rounds chunk", "metrics fetch", "final block",
              "serve")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    text: str = ""  # the op's string stats, joined


@dataclasses.dataclass
class Reduced:
    window: tuple[float, float]
    ops: list
    spans: list
    busy_ns: float
    gaps: list  # (start, end, host span name)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def kernel(self, name: str) -> tuple[int, float]:
        """(calls, device seconds) of the ops that belong to ``name``."""
        calls, ns = 0, 0.0
        for op in self.ops:
            custom = any(m in op.name or m in op.text for m in CUSTOM)
            if custom and (name in op.name or name in op.text):
                calls += 1
                ns += op.end - op.start
        return calls, ns / 1e9

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (by HLO instruction name:
        a TPU op's event name is its whole instruction text) and the
        longest idle gaps, each named by its host span and its offset
        into the window."""
        by_op = defaultdict(float)
        for op in self.ops:
            by_op[op.name.split(" = ", 1)[0]] += (op.end - op.start) / 1e9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[f"{g[2]} @{(g[0] - self.window[0]) / 1e9:.6f}s",
                               (g[1] - g[0]) / 1e9] for g in gaps]}


def _text(ev) -> str:
    parts = []
    for _, value in ev.stats:
        if isinstance(value, str):
            parts.append(value)
    return "\n".join(parts)


def read_planes(path: str):
    """(device ops, host spans) from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = [], []
    device_planes = sorted(
        (p for p in pd.planes if p.name.startswith("/device:TPU:")),
        key=lambda p: p.name)
    if device_planes:
        for line in device_planes[0].lines:
            if line.name == OPS_LINE:
                ops = [Event(e.name, e.start_ns, e.end_ns, _text(e))
                       for e in line.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.append(Event(e.name, e.start_ns, e.end_ns))
    return ops, spans


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def reduce(ops: list, spans: list) -> Reduced:
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if windows:
        w0, w1 = windows[0].start, windows[0].end
    elif ops:
        w0, w1 = min(o.start for o in ops), max(o.end for o in ops)
    else:
        raise ValueError("the trace holds neither a window span nor a "
                         "device op")
    inside = [o for o in ops if o.end > w0 and o.start < w1]
    busy = _union((max(o.start, w0), min(o.end, w1)) for o in inside)
    busy_ns = sum(e - s for s, e in busy)
    holes, cursor = [], w0
    for s, e in busy:
        if s > cursor:
            holes.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < w1:
        holes.append((cursor, w1))
    gaps = [(s, e, _host_label(s, e, spans)) for s, e in holes]
    return Reduced(window=(w0, w1), ops=inside, spans=spans,
                   busy_ns=busy_ns, gaps=gaps)


def _host_label(s: float, e: float, spans: list) -> str:
    """The span inside the window that covers most of the gap (the
    shorter one on a tie); ``window`` where none of them does."""
    best, best_cover, best_len = "no span", 0.0, float("inf")
    inner = [sp for sp in spans if sp.name != WINDOW_SPAN]
    if not any(min(e, sp.end) > max(s, sp.start) for sp in inner):
        inner = spans
    for sp in inner:
        cover = min(e, sp.end) - max(s, sp.start)
        length = sp.end - sp.start
        if cover <= 0:
            continue
        if cover > best_cover * 1.0001 or (
                cover >= best_cover * 0.9999 and length < best_len):
            best, best_cover, best_len = sp.name, cover, length
    return best


def reduce_file(path: str) -> Reduced:
    ops, spans = read_planes(path)
    return reduce(ops, spans)
