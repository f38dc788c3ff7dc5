"""The program's own spans in a traced run, for the per-layer readers of
the round driver, the serve loop, the host runtime and the plan, solve
and consensus layer.

The program names its work ``fedback/<name>`` (``repro.utils.spans``):

* device scopes ``fedback/trigger``, ``plan``, ``solve``, ``commit``
  and ``consensus``: each device op of a round carries one in its
  ``op_name``.  A TPU op's event carries no ``op_name``, but its name
  is its HLO instruction's text and its program is the ``XLA Modules``
  event around it; the profiler keeps each program's HLO, with every
  instruction's ``op_name``, in its ``/host:metadata`` plane;
* host spans ``fedback/round`` (arg ``i``), ``run_rounds.stack``,
  ``serve.tick`` (arg ``t``) with ``serve.upload``, ``serve.step``,
  ``serve.fetch`` and ``serve.ledger`` (args ``committed``,
  ``deferred``), and ``gc`` (arg ``generation``).

``trace_reduce`` keeps only the harness's host spans, so the program's
are read again from the run's ``.xplane.pb``: the file under
``bench_out/`` whose ``window`` span is the reduced window.  A program
older than these spans has none; every reader then returns None.

    python bench/spans.py bench_out/<workload>-<seed>

prints the program's host spans in the window, each scope's device
time and the device time under no scope, the device-idle time under a
program span and by the innermost span over it, and the longest idle
gaps named by the program span that covers most of each.
"""
from __future__ import annotations

import bisect
import dataclasses
import os
import sys

PREFIX = "fedback/"
SCOPES = ("trigger", "plan", "solve", "commit", "consensus")


@dataclasses.dataclass
class Span:
    name: str      # without the prefix
    start: float   # ns, the profiler's clock
    end: float
    args: dict

    @property
    def ms(self) -> float:
        return (self.end - self.start) / 1e6


@dataclasses.dataclass
class Loaded:
    spans: list    # [Span], by start
    modules: list  # (start, end, module name) of the device's runs
    op_names: dict  # module name -> {instruction name: op_name}
    scopes: list = None  # (op, scope) of the reduced window's ops


_LOADED: dict = {}  # window (start, end) -> Loaded


def load(path: str):
    """(window, program spans) of an ``.xplane.pb`` file; the file is
    remembered by its window for :func:`program_spans` and
    :func:`op_scopes`."""
    from jax.profiler import ProfileData

    window, spans, modules = None, [], []
    pd = ProfileData.from_file(path)
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)
    for line in devices[0].lines if devices else ():
        if line.name == "XLA Modules":
            modules = sorted((e.start_ns, e.end_ns, e.name)
                             for e in line.events)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], e.start_ns,
                                      e.end_ns, dict(e.stats)))
                elif e.name == "window" and window is None:
                    window = (e.start_ns, e.end_ns)
    spans.sort(key=lambda s: s.start)
    if window is not None:
        _LOADED[window] = Loaded(spans, modules,
                                 hlo_op_names(path) if modules else {})
    return window, spans


# --- the HLO modules of /host:metadata, read from the raw protobuf -----

def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes):
    """(field number, value) of a protobuf message: ints for varints,
    bytes for length-delimited fields."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _first(buf: bytes, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def hlo_op_names(path: str) -> dict:
    """module name -> {instruction name: op_name}, for every
    instruction of every computation of the HLO modules the trace
    keeps (XSpace.planes[/host:metadata].event_metadata, each with an
    HloProto stat; HloModuleProto.computations[].instructions[].
    metadata.op_name)."""
    with open(path, "rb") as f:
        raw = f.read()
    out = {}
    for number, plane in _fields(raw):
        if number != 1 or _first(plane, 2) != b"/host:metadata":
            continue
        for f, entry in _fields(plane):
            if f != 4:  # event_metadata map entries
                continue
            meta = _first(entry, 2)
            name = _first(meta, 2).decode(errors="replace")
            names = out.setdefault(name, {})
            for g, stat in _fields(meta):
                if g != 5:
                    continue
                proto = _first(stat, 6) or _first(stat, 5)
                module = _first(proto, 1) if proto else b""
                for h, comp in _fields(module) if module else ():
                    if h != 3:
                        continue
                    for k, inst in _fields(comp):
                        if k == 2:
                            op = _first(_first(inst, 7), 2)
                            if op:
                                names[_first(inst, 1).decode()] = \
                                    op.decode(errors="replace")
    return out


def _trace_files() -> list:
    from harness import OUT_DIR

    found = [os.path.join(b, f) for b, _, fs in os.walk(OUT_DIR)
             for f in fs if f.endswith(".xplane.pb")]
    return sorted(found, key=os.path.getmtime, reverse=True)


def _loaded(ctx):
    window = tuple(ctx.trace.window)
    if window not in _LOADED:
        for path in _trace_files():
            if load(path)[0] == window:
                break
    return _LOADED.get(window)


def program_spans(ctx) -> list:
    """The program's host spans of the traced run that ``ctx`` reduces
    (all of them, also outside the window); [] where it has none."""
    found = _loaded(ctx)
    return found.spans if found else []


def scope_of(op_name: str):
    """The one scope named in an ``op_name``, or None."""
    hits = [name for name in SCOPES if PREFIX + name + "/" in op_name]
    return hits[0] if len(hits) == 1 else None


def op_scopes(ctx) -> list:
    """Each op of ``ctx.trace.ops`` with its scope: (op, name or None)."""
    found = _loaded(ctx) or Loaded([], [], {})
    if found.scopes is None:
        starts = [m[0] for m in found.modules]
        found.scopes = []
        for op in ctx.trace.ops:
            i = bisect.bisect_right(starts, op.start) - 1
            name = None
            if i >= 0 and op.end <= found.modules[i][1]:
                inst = op.name.split(" = ", 1)[0].lstrip("%")
                name = scope_of(found.op_names.get(
                    found.modules[i][2], {}).get(inst, ""))
            found.scopes.append((op, name))
    return found.scopes


def in_window(ctx, name: str) -> list:
    """The spans named ``name`` that start inside the window."""
    w0, w1 = ctx.trace.window
    return [s for s in program_spans(ctx)
            if s.name == name and w0 <= s.start < w1]


def union_ns(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def device_ms(ctx, name: str):
    """Device time of scope ``name`` per step (round or tick), in ms:
    the union of its ops' intervals inside the window, so that a while
    op and the ops of its body count once."""
    w0, w1 = ctx.trace.window
    intervals = [(max(op.start, w0), min(op.end, w1))
                 for op, scope in op_scopes(ctx) if scope == name]
    if not intervals or not ctx.steps:
        return None
    return union_ns(intervals) / 1e6 / ctx.steps


def gc_share(ctx):
    """Collector time inside the window as a share of it, in %; None
    where the program wrote no span at all."""
    spans = program_spans(ctx)
    if not spans:
        return None
    w0, w1 = ctx.trace.window
    gc = [(max(s.start, w0), min(s.end, w1)) for s in spans
          if s.name == "gc" and s.end > w0 and s.start < w1]
    return 100.0 * union_ns(gc) / (w1 - w0)


def label_gaps(gaps, spans) -> list:
    """(start, end, label) for each ``(start, end)`` idle gap: the
    program span that covers most of it, the shorter one on a tie (so
    the innermost); ``None`` where no program span covers it."""
    out = []
    for s, e in gaps:
        best, cover_best, len_best = None, 0.0, float("inf")
        for sp in spans:
            cover = min(e, sp.end) - max(s, sp.start)
            if cover <= 0:
                continue
            length = sp.end - sp.start
            if cover > cover_best * 1.0001 or (
                    cover >= cover_best * 0.9999 and length < len_best):
                best, cover_best, len_best = sp.name, cover, length
        out.append((s, e, best))
    return out


def idle_by_span(gaps, spans) -> dict:
    """Idle ns of ``gaps`` by the innermost (shortest) program span over
    each instant; ``None`` for idle time under no span."""
    out: dict = {}
    for s, e in gaps:
        over = [sp for sp in spans if sp.end > s and sp.start < e]
        cuts = sorted({s, e} | {t for sp in over for t in (sp.start, sp.end)
                                if s < t < e})
        for a, b in zip(cuts, cuts[1:]):
            inner = [sp for sp in over if sp.start <= a and sp.end >= b]
            name = min(inner, key=lambda sp: sp.end - sp.start).name \
                if inner else None
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def main(path: str, top: int = 10) -> None:
    import types

    from trace_reduce import reduce_file

    if os.path.isdir(path):
        path = next(os.path.join(b, f) for b, _, fs in os.walk(path)
                    for f in fs if f.endswith(".xplane.pb"))
    red = reduce_file(path)
    window, spans = load(path)
    found = _LOADED[window]
    ctx = types.SimpleNamespace(trace=red, steps=1)
    w0, w1 = red.window
    busy_ms = red.busy_ns / 1e6
    print(f"window {red.window_s * 1e3:.3f} ms, busy {busy_ms:.3f} ms, "
          f"program spans {len(spans)}")
    inside = [sp for sp in spans if w0 <= sp.start < w1]
    for name in sorted({sp.name for sp in inside}):
        ms = [sp.ms for sp in inside if sp.name == name]
        print(f"span {name}: {len(ms)} in the window, mean "
              f"{sum(ms) / len(ms):.4f} ms, total {sum(ms):.3f} ms")
    starts = [m[0] for m in found.modules]
    scoped, unscoped = [], {}
    for op, name in op_scopes(ctx):
        iv = (max(op.start, w0), min(op.end, w1))
        if name is not None:
            scoped.append(iv + (name,))
            continue
        i = bisect.bisect_right(starts, op.start) - 1
        module = found.modules[i][2].split("(")[0] if i >= 0 else "?"
        inst = op.name.split(" = ", 1)[0]
        key = (module, inst if module == "jit_program" else "*")
        unscoped.setdefault(key, []).append(iv)
    for name in SCOPES:
        iv = [(s, e) for s, e, n in scoped if n == name]
        print(f"scope {name}: {union_ns(iv) / 1e6:.3f} ms, {len(iv)} ops")
    if busy_ms > 0:
        cover = union_ns((s, e) for s, e, _ in scoped) / 1e6 / busy_ms
        print(f"scopes cover {cover:.2%} of busy time")
    rest = sorted(((union_ns(iv) / 1e6, k, len(iv))
                   for k, iv in unscoped.items()), reverse=True)
    for ms, (module, inst), n in rest[:top]:
        print(f"unscoped {module} {inst}: {ms:.3f} ms, {n} ops")
    gaps = [(s, e) for s, e, _ in red.gaps]
    by_span = idle_by_span(gaps, spans)
    idle = sum(by_span.values())
    if idle > 0:
        print(f"idle under a program span: "
              f"{1 - by_span.get(None, 0.0) / idle:.2%}")
    for label, ns in sorted(by_span.items(), key=lambda kv: -kv[1]):
        print(f"idle innermost {label}: {ns / 1e6:.3f} ms")
    longest = sorted(label_gaps(gaps, spans), key=lambda g: g[0] - g[1])
    for s, e, label in longest[:top]:
        print(f"gap {label} @{(s - w0) / 1e9:.6f}s {(e - s) / 1e6:.3f} ms")


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main(*sys.argv[1:])
