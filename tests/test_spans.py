"""Named spans and scopes (repro.utils.spans) and the benchmark's
readers of them (bench/spans.py, bench/metrics/).

* the host spans of ``run_rounds``, ``serve()`` and the host backend
  land in a real ``jax.profiler`` trace, one per round, tick or phase,
  with their arguments; a collector pass inside a call is a
  ``fedback/gc`` span, and ``gc.callbacks`` is left as it was;
* the five device scopes reach the round program's op metadata and
  change nothing else of it;
* on hand-built traces each new reader reads its value, the existing
  reduction and readers read the same with and without the program's
  spans, and idle gaps are named by the program span over them.
"""
import contextlib
import gc
import os

import jax
import numpy as np
import pytest

from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn, run_rounds
from repro.core.schedule import TraceConfig, make_trace, serve
from repro.data import make_least_squares
from repro.utils import spans as prog_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench")
SCOPES = ("trigger", "plan", "solve", "commit", "consensus")
N = 8


def _engine(*, compact=True, fused=False, arrivals=False, **kw):
    data, params0, loss = make_least_squares(N, 6, 4)
    spec = make_flat_spec(params0)
    cfg = FLConfig(algorithm="fedback", n_clients=N, participation=0.5,
                   rho=1.0, lr=0.1, momentum=0.0, epochs=1, batch_size=3,
                   controller=ControllerConfig(K=0.2, alpha=0.9),
                   compact=compact, fused_gss=fused, **kw)
    state = init_state(cfg, params0, spec=spec)
    round_fn = make_round_fn(cfg, loss, data, spec=spec,
                             arrivals_arg=arrivals)
    return round_fn, state


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = next(os.path.join(b, f) for b, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                        for e in line.events
                        if e.name.startswith("fedback/")]
    return out


# --- (a) host spans in a real trace ---------------------------------------

def test_run_rounds_spans_and_gc(tmp_path):
    round_fn, state = _engine()
    state, _ = run_rounds(round_fn, state, 1)  # compile off the trace

    def collecting(st):
        gc.collect()
        return round_fn(st)

    n_callbacks = len(gc.callbacks)
    with jax.profiler.trace(str(tmp_path)):
        state, m = run_rounds(collecting, state, 4)
        jax.block_until_ready((state, m))
    assert len(gc.callbacks) == n_callbacks
    ev = _host_events(tmp_path)
    rounds = [e for e in ev if e[0] == "fedback/round"]
    assert sorted(e[3]["i"] for e in rounds) == [0, 1, 2, 3]
    assert sum(e[0] == "fedback/run_rounds.stack" for e in ev) == 1
    gcs = [e for e in ev if e[0] == "fedback/gc"]
    # One forced full collection inside each round's dispatch.
    full = [g for g in gcs if g[3]["generation"] == 2]
    assert len(full) >= 4
    for r in rounds:
        assert any(r[1] <= g[1] and g[2] <= r[2] for g in full)


def test_trace_keeps_the_round_modules_scopes(tmp_path, monkeypatch):
    """The HLO modules a trace keeps name each instruction's scope: the
    path by which the benchmark maps a device op to its layer."""
    monkeypatch.syspath_prepend(BENCH)
    import spans

    round_fn, state = _engine()
    with jax.profiler.trace(str(tmp_path)):
        state, m = run_rounds(round_fn, state, 2)
        jax.block_until_ready((state, m))
    path = next(os.path.join(b, f) for b, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    found = {spans.scope_of(op) for names in
             spans.hlo_op_names(path).values() for op in names.values()}
    assert set(SCOPES) <= found


def test_serve_tick_spans(tmp_path):
    round_fn, state = _engine(arrivals=True)
    trace = make_trace(TraceConfig(kind="bursty", n_clients=N, ticks=6,
                                   seed=1))
    state, _ = serve(round_fn, state, trace[:1])  # compile off the trace
    with jax.profiler.trace(str(tmp_path)):
        state, rep = serve(round_fn, state, trace[1:])
    ev = _host_events(tmp_path)
    ticks = sorted((e for e in ev if e[0] == "fedback/serve.tick"),
                   key=lambda e: e[1])
    assert [e[3]["t"] for e in ticks] == [0, 1, 2, 3, 4]
    for child in ("upload", "step", "fetch", "ledger"):
        inner = [e for e in ev if e[0] == "fedback/serve." + child]
        assert len(inner) == len(ticks)
        for tick in ticks:
            assert sum(tick[1] <= e[1] and e[2] <= tick[2]
                       for e in inner) == 1
    ledgers = sorted((e for e in ev if e[0] == "fedback/serve.ledger"),
                     key=lambda e: e[1])
    assert ledgers[-1][3]["deferred"] == rep.final_num_deferred
    assert all(set(e[3]) == {"committed", "deferred"} for e in ledgers)


def test_host_backend_phase_spans(tmp_path):
    round_fn, state = _engine(state_backend="host")
    state, _ = round_fn(state)  # compile and seed the distances
    with jax.profiler.trace(str(tmp_path)):
        state, m = run_rounds(round_fn, state, 2)
        jax.block_until_ready(m)
    names = [e[0] for e in _host_events(tmp_path)]
    for phase, per_round in (("plan", 1), ("h2d", 2), ("solve", 1),
                             ("d2h", 1), ("scatter", 1), ("agg", 1)):
        assert names.count("fedback/host." + phase) == 2 * per_round
    assert round_fn.stats["rounds"] == 3
    assert not any(k.endswith("_s") for k in round_fn.stats)


def test_gc_spans_install_one_hook():
    before = list(gc.callbacks)
    with prog_spans.gc_spans():
        with prog_spans.gc_spans():
            assert gc.callbacks.count(prog_spans._on_gc) == 1
        assert gc.callbacks.count(prog_spans._on_gc) == 1
        gc.collect()
    assert gc.callbacks == before
    with pytest.raises(RuntimeError):
        with prog_spans.gc_spans():
            raise RuntimeError("the hook goes all the same")
    assert gc.callbacks == before


# --- (b) device scopes ----------------------------------------------------

def _strip_metadata(hlo: str) -> str:
    import re

    return re.sub(r", metadata=\{[^}]*\}", "", hlo)


@pytest.mark.parametrize("kind", ["dense", "compact", "compact_fused"])
def test_scopes_in_metadata_only(kind, monkeypatch):
    kw = {"dense": dict(compact=False), "compact": {},
          "compact_fused": dict(fused=True)}[kind]
    round_fn, state = _engine(**kw)
    lowered = round_fn.lower(state)
    text = lowered.as_text(debug_info=True)
    for name in SCOPES:
        assert f"fedback/{name}/" in text, name
    compiled = lowered.compile().as_text()
    assert "fedback/solve/" in compiled

    import repro.core.compact as compact_mod
    import repro.core.fedback as fedback_mod

    def off(name):
        return contextlib.nullcontext()

    monkeypatch.setattr(fedback_mod, "scope", off)
    monkeypatch.setattr(compact_mod, "scope", off)
    bare_fn, bare_state = _engine(**kw)
    bare = bare_fn.lower(bare_state)
    assert "fedback/" not in bare.as_text(debug_info=True)
    assert bare.as_text() == lowered.as_text()
    bare_hlo = _strip_metadata(bare.compile().as_text())
    assert bare_hlo.count(" = ") == _strip_metadata(compiled).count(" = ")


# --- (c) the readers on hand-built traces ---------------------------------
#
# As on a TPU: a device op's event name is its HLO instruction text, its
# program is the ``XLA Modules`` event around it, and the program's HLO
# (with each instruction's ``op_name``) sits in ``/host:metadata``.

def _proto(*fields):
    """Protobuf bytes of (field number, bytes or str) pairs."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        value = value.encode() if isinstance(value, str) else value
        out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def _plane(pid, pname, lines, extra=""):
    """One plane of a text-proto XSpace: ``lines`` of (name, events),
    events (name, start us, length us, {stat: int})."""
    names, stats, body = {}, {}, ""
    for lid, (line, events) in enumerate(lines, 1):
        evs = []
        for name, start, length, st in events:
            mid = names.setdefault(name, len(names) + 1)
            sts = "".join(f" stats {{ metadata_id: "
                          f"{stats.setdefault(k, len(stats) + 1)} "
                          f"int64_value: {v} }}" for k, v in st.items())
            evs.append(f"events {{ metadata_id: {mid} "
                       f"offset_ps: {int(start * 1e6)} "
                       f"duration_ps: {int(length * 1e6)}{sts} }}")
        body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n'
                 + "\n".join(evs) + "}\n")
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in names.items())
    smeta = "".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}\n' for n, i in stats.items())
    return (f'planes {{ id: {pid} name: "{pname}"\n'
            f"{body}{meta}{smeta}{extra}}}\n")


def _xspace(ops, host, modules, scoped):
    """A trace: ``ops`` (instruction, opcode, start us, length us,
    scope, path) on the TPU's ``XLA Ops`` line, ``modules`` (name,
    start us, length us) on its ``XLA Modules`` line, ``host`` spans on
    one host line; the program ``jit_program(7)`` gives each
    instruction the ``op_name`` "jit(program)/[fedback/<scope>/]path",
    with the scope only where ``scoped``."""
    insts = [(1, _proto((1, inst), (7, _proto((2, "jit(program)/" + (
        f"fedback/{scope}/" if scoped and scope else "") + path)))))
        for inst, _, _, _, scope, path in ops if path]
    hlo = _proto((1, _proto((1, "jit_program"), (3, _proto(
        (1, "main"), *[(2, i) for _, i in insts])))))
    octal = "".join(f"\\{b:03o}" for b in hlo)
    device = _plane(1, "/device:TPU:0", [
        ("XLA Ops", [(f"%{inst} = f32[16] {opcode}(f32[16] %p)", t, d, {})
                     for inst, opcode, t, d, _, _ in ops]),
        ("XLA Modules", [(n, t, d, {}) for n, t, d in modules])])
    meta = _plane(3, "/host:metadata", [], extra=(
        'event_metadata { key: 1 value { id: 1 name: "jit_program(7)" '
        f'stats {{ metadata_id: 1 bytes_value: "{octal}" }} }} }}\n'
        'stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }\n'))
    return device + _plane(2, "/host:CPU", [("python", host)]) + meta


def _rounds_trace(scoped):
    ops = [("trigger_sq_norms.1", "custom-call", 1, 1, "trigger",
            "jit(trigger_sq_norms)/pallas_call"),
           ("pad.3", "pad", 2, 0.5, "trigger", "jit(trigger_sq_norms)/pad"),
           ("sort.4", "sort", 2.5, 0.5, "plan", "sort"),
           ("while.2", "while", 3, 4, "solve", "while"),
           ("fusion.5", "fusion", 4, 2, "solve", "while/body/dot"),
           ("fused_gss.1", "custom-call", 7, 1, "commit",
            "jit(fused_gss)/pallas_call"),
           ("fusion.9", "fusion", 8, 1, "consensus", "reduce_sum"),
           ("copy-done.3", "copy-done", 8.5, 0.5, None, ""),
           ("concatenate.1", "concatenate", 15, 1, None, "")]
    modules = [("jit_program(7)", 1, 8), ("jit_concatenate(8)", 15, 1)]
    host = [("window", 0, 20, {}), ("run_rounds chunk", 0, 16, {}),
            ("metrics fetch", 16, 4, {})]
    if scoped:
        host += [("fedback/round", 0, 3, {"i": 0}),
                 ("fedback/round", 3, 3, {"i": 1}),
                 ("fedback/run_rounds.stack", 6, 4, {}),
                 ("fedback/gc", 10, 4, {"generation": 2}),
                 ("fedback/round", 30, 3, {"i": 2})]  # after the window
    return _xspace(ops, host, modules, scoped)


def _serve_trace(scoped):
    ops = [("trigger_sq_norms.1", "custom-call", 2, 1, "trigger",
            "jit(trigger_sq_norms)/pallas_call"),
           ("while.2", "while", 3, 2, "solve", "while"),
           ("trigger_sq_norms.1", "custom-call", 11, 1, "trigger",
            "jit(trigger_sq_norms)/pallas_call"),
           ("while.2", "while", 12, 3, "solve", "while")]
    modules = [("jit_program(7)", 2, 3), ("jit_program(7)", 11, 4)]
    host = [("window", 0, 20, {}), ("serve", 0, 20, {})]
    if scoped:
        for t, (t0, step, fetch, ledger, deferred) in enumerate(
                [(1, 1, 3, 2, 3), (10, 1, 4, 2, 5)]):
            host += [("fedback/serve.tick", t0, 1 + step + fetch + ledger,
                      {"t": t}),
                     ("fedback/serve.upload", t0, 1, {}),
                     ("fedback/serve.step", t0 + 1, step, {}),
                     ("fedback/serve.fetch", t0 + 1 + step, fetch, {}),
                     ("fedback/serve.ledger", t0 + 1 + step + fetch,
                      ledger, {"committed": 2, "deferred": deferred})]
    return _xspace(ops, host, modules, scoped)


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    import harness
    import spans

    monkeypatch.setattr(spans, "_LOADED", {})
    return harness, spans


def _ctx(text, kind, steps, out_dir):
    """A reader's context for the trace ``text``, written where a run's
    trace goes (``out_dir`` stands for ``bench_out/``)."""
    from jax.profiler import ProfileData

    from metrics_ctx import Context
    from trace_reduce import reduce_file

    trace_dir = os.path.join(out_dir, "cell-1", "plugins")
    os.makedirs(trace_dir)
    path = os.path.join(trace_dir, "hand.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    committed = np.zeros((steps, 4), bool)
    committed[:, 0] = True
    return Context(kind=kind, cfg={"epochs": 1, "batch_size": 2},
                   n_clients=4, dim=8, capacity=2, wall_s=20e-6,
                   steps=steps, committed=committed,
                   sizes=np.full(4, 6), flops_per_example=10,
                   peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
                   trace=reduce_file(path))


def _read_all(bench, ctx, out_dir, monkeypatch):
    """Every metric's reading of ``ctx``, the run's trace found under
    ``out_dir``."""
    harness, spans = bench
    monkeypatch.setattr(harness, "OUT_DIR", out_dir)
    monkeypatch.setattr(spans, "_LOADED", {})
    return {m["name"]: harness.metric_reader(m["name"]).read(ctx)
            for m in harness.benchmark()["per_layer"]}


EXISTING = ("device_idle_share.train", "round_mfu",
            "trigger_sq_norms_roofline", "fused_gss_roofline",
            "device_idle_share.serve")

ROUNDS_EXPECTED = {  # per round of 2, in ms (trace times are in us)
    "trigger_ms.train": 0.75e-3, "plan_ms.train": 0.25e-3,
    "solve_ms.train": 2e-3, "commit_ms.train": 0.5e-3,
    "consensus_ms.train": 0.5e-3, "round_host_ms": 3e-3,
    "gc_pause_share.train": 20.0,
}
SERVE_EXPECTED = {  # per tick of 2
    "trigger_ms.serve": 1e-3, "solve_ms.serve": 2.5e-3,
    "tick_fetch_ms": 3.5e-3, "tick_host_ms": 4e-3,
    "queue_depth_p95": 3 + 0.95 * 2, "gc_pause_share.serve": 0.0,
}


@pytest.mark.parametrize("kind", ["rounds", "serve"])
def test_readers_on_hand_traces(bench, kind, tmp_path, monkeypatch):
    make = _rounds_trace if kind == "rounds" else _serve_trace
    expected = ROUNDS_EXPECTED if kind == "rounds" else SERVE_EXPECTED
    bare_dir, scoped_dir = str(tmp_path / "bare"), str(tmp_path / "scoped")
    bare = _ctx(make(False), kind, 2, bare_dir)
    scoped = _ctx(make(True), kind, 2, scoped_dir)
    # The reduction reads the same from both, the kernels included.
    assert scoped.trace.window == bare.trace.window
    assert scoped.trace.busy_ns == bare.trace.busy_ns
    for k in ("trigger_sq_norms", "fused_gss"):
        assert scoped.trace.kernel(k) == bare.trace.kernel(k)
    assert scoped.trace.kernel("trigger_sq_norms")[0] == 2 - (
        kind == "rounds")
    assert scoped.trace.kernel("fused_gss")[0] == (kind == "rounds")
    assert scoped.trace.gaps == bare.trace.gaps
    before = _read_all(bench, bare, bare_dir, monkeypatch)
    after = _read_all(bench, scoped, scoped_dir, monkeypatch)
    for name in EXISTING:
        assert after[name] == before[name], name
    # The new readers: a value from the program's spans, None without.
    new = set(before) - set(EXISTING)
    assert set(expected) <= new
    for name in new:
        assert before[name] is None, name
        if name in expected:
            assert after[name] == pytest.approx(expected[name]), name


def test_op_scopes_from_the_traced_modules(bench, tmp_path, monkeypatch):
    harness, spans = bench
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    ctx = _ctx(_rounds_trace(True), "rounds", 2, str(tmp_path))
    got = [(op.name.split(" = ")[0], name)
           for op, name in spans.op_scopes(ctx)]
    # The copy has no op_name, the concatenate another program.
    assert got == [("%trigger_sq_norms.1", "trigger"), ("%pad.3", "trigger"),
                   ("%sort.4", "plan"), ("%while.2", "solve"),
                   ("%fusion.5", "solve"), ("%fused_gss.1", "commit"),
                   ("%fusion.9", "consensus"), ("%copy-done.3", None),
                   ("%concatenate.1", None)]
    assert spans.scope_of("jit(program)/fedback/solve/while/body") == "solve"
    assert spans.scope_of("jit(program)/while") is None


def test_gaps_named_by_program_spans(bench, tmp_path, monkeypatch):
    harness, spans = bench
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    ctx = _ctx(_rounds_trace(True), "rounds", 2, str(tmp_path))
    prog = spans.program_spans(ctx)  # found by its window
    assert len(prog) == 5
    gaps = [(s, e) for s, e, _ in ctx.trace.gaps]
    assert [g[2] for g in ctx.trace.gaps] == [
        "run_rounds chunk", "run_rounds chunk", "metrics fetch"]
    labels = [label for _, _, label in spans.label_gaps(gaps, prog)]
    # 0-1 us under round 0; 9-15 us mostly under the collector (10-14),
    # the rest under the stack; 16-20 us under no program span.
    assert labels == ["round", "gc", None]
    by_span = {k: v / 1e3 for k, v in spans.idle_by_span(gaps, prog).items()}
    assert by_span == pytest.approx(
        {"round": 1, "run_rounds.stack": 1, "gc": 4, None: 5})
    assert [s.args["i"] for s in spans.in_window(ctx, "round")] == [0, 1]
    assert spans.union_ns([(3, 7), (4, 6), (1, 2), (2, 2.5)]) == 5.5
