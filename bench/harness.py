"""The benchmark harness: one cell of ``BENCHMARK.json``, one run.

Everything a cell needs is found by name: its configuration in
``bench/configs/<config>.json`` with the model beside it in
``<config>.py``, its traffic in ``bench/traffic/<traffic>.json``, the
limits of its comparison in ``bench/limits/<cell>.json`` and each
per-layer metric's reader in ``bench/metrics/<metric>.py``.

A run builds the data and the starting weights from the seed, builds
the program's round with ``make_round_fn`` and drives it the way the
traffic says:

* ``rounds``: ``run_rounds`` calls of ``chunk_rounds`` rounds each, the
  length of a user's training run, until ``--seconds`` have passed, at
  most two calls in flight, then a block on the final state;
* ``serve``: one ``serve()`` call over as many ticks of the arrival
  trace as fill ``--seconds`` at the tick time of a calibration call.

Set-up ends where the window starts.  Its first call runs the first
``check_rounds`` rounds (``check_ticks`` ticks) from the seed through
the window's own entry, as a call of their own; those are what the
reference is compared with once the window has closed.  A rounds cell
then warms up one call of ``chunk_rounds`` before the window.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
OUT_DIR = os.path.join(ROOT, "bench_out")
KERNELS = ("trigger_sq_norms", "fused_gss")


class BenchError(RuntimeError):
    """The run cannot be made as the cell asks."""


# --- finding the pieces of a cell by name ------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    model: object
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bm: dict | None = None) -> Cell:
    bm = benchmark() if bm is None else bm
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    entry = configs[w["config"]]
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    model = load_module(os.path.join(BENCH, "configs", w["config"] + ".py"),
                        "bench_model_" + w["config"])
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     w["traffic"] + ".json"))
    limits = load_json(os.path.join(BENCH, "limits", name + ".json"))
    return Cell(name=name, chips=w["chips"], cfg=cfg, model=model,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


# --- the program ---------------------------------------------------------

def import_program():
    """Import ``repro`` from this checkout's ``src`` and nowhere else."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise BenchError(f"no program under {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro

    paths = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(SRC + os.sep) for p in paths):
        raise BenchError(f"repro resolved to {paths}, not {SRC}")
    return repro


def fl_config(cfg: dict, seed: int):
    from repro.core import ControllerConfig, FLConfig

    k = cfg["kernels"]
    return FLConfig(
        algorithm="fedback", n_clients=cfg["n_clients"],
        participation=cfg["participation"], rho=cfg["rho"], lr=cfg["lr"],
        momentum=cfg["momentum"], epochs=cfg["epochs"],
        batch_size=cfg["batch_size"],
        controller=ControllerConfig(K=cfg["K"], alpha=cfg["alpha"],
                                    delta0=cfg["delta0"]),
        compact=cfg["compact"], capacity_slack=cfg["capacity_slack"],
        use_trigger_kernel=k, use_admm_kernel=k, fused_gss=k, seed=seed)


class CompileCounter:
    """Counts backend compiles and persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}


def setup_jax(require_tpu: bool, chips: int):
    """Device check and the persistent cache at its fixed path."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchError(f"no TPU: JAX platform is {platform!r}")
    if platform != "cpu":
        # Every program this run compiles goes to the cache, at a path
        # fixed inside the checkout, so that only a cell's first run
        # compiles.  (CPU test runs keep no cache.)
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def set_precision(cfg: dict) -> None:
    """The float32 matmul precision the configuration states:
    "default" (the TPU's own, bf16 passes) or "highest"."""
    import jax

    prec = cfg["matmul_precision"]
    jax.config.update("jax_default_matmul_precision",
                      None if prec == "default" else prec)


# --- one run -------------------------------------------------------------

@dataclasses.dataclass
class Problem:
    data: dict
    layout: object
    params0: dict
    fl: object
    spec: object
    ragged: object
    loss_fn: object


def build_problem(cell: Cell, seed: int) -> Problem:
    import jax

    from datagen import make_data
    from repro.core import make_flat_spec
    from repro.utils.ragged import make_ragged_spec

    key = jax.random.PRNGKey(seed)
    data, layout = make_data(cell.cfg, jax.random.fold_in(key, 1))
    params0 = jax.jit(lambda k: cell.model.init_params(k, cell.cfg))(
        jax.random.fold_in(key, 2))
    ragged = None
    if layout is not None:
        ragged = make_ragged_spec([int(s) for s in layout[0]])
        if ragged.buffer_rows != data["x"].shape[0]:
            raise BenchError(f"pooled buffer has {data['x'].shape[0]} rows, "
                             f"the spec wants {ragged.buffer_rows}")
    jax.block_until_ready((data, params0))
    return Problem(data=data, layout=layout, params0=params0,
                   fl=fl_config(cell.cfg, seed),
                   spec=make_flat_spec(params0), ragged=ragged,
                   loss_fn=cell.model.program_loss(cell.cfg))


def host_peak_gb() -> float:
    """This process's peak resident host memory so far, in GB."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def host_state(state) -> dict:
    import jax

    return {k: np.asarray(jax.device_get(v)) for k, v in
            (("theta", state.theta), ("lam", state.lam),
             ("z", state.z_prev), ("omega", state.omega))}


def host_metrics(m) -> list:
    """Per-round dicts of host arrays from stacked RoundMetrics."""
    import jax

    fields = {"events": m.events, "distances": m.distances,
              "delta": m.delta, "committed": m.committed,
              "train_loss": m.train_loss}
    fields = {k: np.asarray(jax.device_get(v)) for k, v in fields.items()}
    return [{k: v[i] for k, v in fields.items()}
            for i in range(fields["events"].shape[0])]


class Recorder:
    """Wraps the served round: keeps each tick's metrics (device
    arrays ``serve()`` fetches anyway) for the ledger check."""

    def __init__(self, round_fn):
        self.round_fn = round_fn
        self.log = []

    def __call__(self, state, arrivals):
        state, m = self.round_fn(state, arrivals)
        self.log.append(m)
        return state, m


class Tracer:
    """``jax.profiler`` around the window when ``--trace 1``."""

    def __init__(self, on: bool, workload: str, seed: int):
        self.on = on
        self.dir = os.path.join(OUT_DIR, f"{workload}-{seed}")

    def __enter__(self):
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.on:
            import jax

            jax.profiler.stop_trace()
        return False

    def span(self, name: str):
        import contextlib

        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def xplane(self) -> str | None:
        for base, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(base, f)
        return None


def say(**fields) -> None:
    print(" ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def rounds_window(round_fn, state, chunk: int, seconds: float, tracer):
    """``run_rounds`` chunks until ``seconds`` have passed; returns the
    final state, the chunks' metrics, rounds done and the wall time."""
    import jax

    from repro.core import run_rounds

    history, pending, rounds = [], None, 0
    t0 = time.perf_counter()
    deadline = t0 + seconds
    with tracer.span("window"):
        while True:
            with tracer.span("run_rounds chunk"):
                state, m = run_rounds(round_fn, state, chunk)
            history.append(m)
            rounds += chunk
            if pending is not None:
                with tracer.span("metrics fetch"):
                    jax.block_until_ready(pending)
            pending = m
            if time.perf_counter() >= deadline:
                break
        with tracer.span("final block"):
            jax.block_until_ready((state, history[-1]))
    return state, history, rounds, time.perf_counter() - t0


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, overrides: dict | None = None,
        traffic_overrides: dict | None = None, round_hook=None,
        t_start: float | None = None) -> dict:
    """One run of a cell; returns the result line as a dict.

    ``overrides`` and ``traffic_overrides`` replace configuration and
    traffic keys (small CPU test runs); ``round_hook(round_fn) ->
    round_fn`` wraps the program's round (fault tests)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = resolve(workload)
    if overrides:
        cell.cfg = dict(cell.cfg, **overrides)
    if traffic_overrides:
        cell.traffic = dict(cell.traffic, **traffic_overrides)
    phases = {}
    devices = setup_jax(require_tpu, cell.chips)
    set_precision(cell.cfg)
    import jax

    counter = CompileCounter()
    import_program()
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    phases["init_s"] = time.perf_counter() - t_start

    t = time.perf_counter()
    problem = build_problem(cell, seed)
    phases["data_s"] = time.perf_counter() - t

    from repro.core import init_state, make_round_fn

    serving = cell.traffic["kind"] == "serve"
    t = time.perf_counter()
    state = init_state(problem.fl, problem.params0, spec=problem.spec)
    jax.block_until_ready(state)
    phases["placement_s"] = time.perf_counter() - t
    round_fn = make_round_fn(problem.fl, problem.loss_fn, problem.data,
                             spec=problem.spec, ragged=problem.ragged,
                             arrivals_arg=serving)
    if round_hook is not None:
        round_fn = round_hook(round_fn)

    tracer = Tracer(trace, workload, seed)
    if serving:
        out = _serve_cell(cell, round_fn, state, seed, seconds, tracer,
                          phases, counter, t_start)
    else:
        out = _rounds_cell(cell, round_fn, state, seconds, tracer, phases,
                           counter, t_start)
    return _finish(cell, problem, seed, trace, devices, tracer, out)


def first_rounds(cell, round_fn, state):
    """The first ``check_rounds`` from the seed, one ``run_rounds`` call
    through the window's own round: (state, per-round host metrics,
    host state after them)."""
    import jax

    from repro.core import run_rounds

    state, m = run_rounds(round_fn, state, cell.traffic["check_rounds"])
    jax.block_until_ready((state, m))
    return state, host_metrics(m), host_state(state)


def first_ticks(cell, round_fn, state, trace):
    """The first ``check_ticks`` of the trace through ``serve()`` from
    the seed: (state, per-tick host metrics, host state, recorder)."""
    import jax

    from repro.core.schedule import serve

    rec = Recorder(round_fn)
    state, _ = serve(rec, state, trace[:cell.traffic["check_ticks"]],
                     warmup=True)
    jax.block_until_ready(state)
    # The first call is serve()'s warm-up probe on a copy of the state.
    metrics = [host_metrics(jax.tree.map(lambda x: x[None], m))[0]
               for m in rec.log[1:]]
    return state, metrics, host_state(state), rec


def _rounds_cell(cell, round_fn, state, seconds, tracer, phases, counter,
                 t_start):
    import jax

    from repro.core import run_rounds

    chunk = cell.traffic["chunk_rounds"]
    t = time.perf_counter()
    state, prog_metrics, prog_state = first_rounds(cell, round_fn, state)
    phases["compile_check_rounds_s"] = time.perf_counter() - t
    t = time.perf_counter()
    state, m = run_rounds(round_fn, state, chunk)
    jax.block_until_ready((state, m))
    del m
    phases["warmup_chunk_s"] = time.perf_counter() - t
    compiled = counter.snapshot()
    setup_s = time.perf_counter() - t_start
    say(phase="setup", setup_s=setup_s, **phases, **compiled,
        host_peak_gb=host_peak_gb())

    window = cell.traffic["trace_seconds"] if tracer.on else seconds
    window = min(window, seconds)
    before = counter.compiles
    with tracer:
        state, history, rounds, wall = rounds_window(
            round_fn, state, chunk, window, tracer)
    committed = np.concatenate([np.asarray(jax.device_get(h.committed))
                                for h in history])
    say(phase="window", rounds=rounds, wall_s=wall,
        compiles_in_window=counter.compiles - before,
        host_peak_gb=host_peak_gb())
    peak = _peak_bytes()
    del state, history, round_fn
    gc.collect()
    return {"setup_s": setup_s, "wall_s": wall, "rounds": rounds,
            "committed": committed, "peak_bytes": peak,
            "prog_metrics": prog_metrics, "prog_state": prog_state,
            "arrivals": None, "ledger": None}


def _serve_cell(cell, round_fn, state, seed, seconds, tracer, phases,
                counter, t_start):
    import jax

    from arrivals import make_arrivals
    from repro.core.schedule import serve

    tr = cell.traffic
    n = cell.cfg["n_clients"]
    trace = make_arrivals(tr["arrivals"], n, tr["max_ticks"], seed)
    check = tr["check_ticks"]
    t = time.perf_counter()
    state, prog_metrics, prog_state, rec = first_ticks(cell, round_fn,
                                                       state, trace)
    phases["compile_first_ticks_s"] = time.perf_counter() - t
    compiled = counter.snapshot()

    calib = tr["calibration_ticks"]
    rec.log.clear()
    t = time.perf_counter()
    state, rep = serve(rec, state, trace[check:check + calib])
    jax.block_until_ready(state)
    phases["calibration_s"] = time.perf_counter() - t
    tick_s = rep.wall_s / calib
    pending0 = np.asarray(jax.device_get(state.queue.age)) > 0
    setup_s = time.perf_counter() - t_start
    say(phase="setup", setup_s=setup_s, tick_s=tick_s, **phases, **compiled,
        host_peak_gb=host_peak_gb())

    window = min(tr["trace_seconds"], seconds) if tracer.on else seconds
    start = check + calib
    ticks = max(1, int(window / tick_s))
    if start + ticks > trace.shape[0]:
        raise BenchError(f"{ticks} ticks do not fit the {trace.shape[0]}-"
                         "tick trace: raise max_ticks")
    window_trace = trace[start:start + ticks]
    rec.log.clear()
    before = counter.compiles
    with tracer, tracer.span("window"), tracer.span("serve"):
        state, rep = serve(rec, state, window_trace, warmup=True)
    log = rec.log[1:]  # the first call is serve()'s warm-up probe
    events = np.stack([np.asarray(m.events) for m in log])
    committed = np.stack([np.asarray(m.committed) for m in log])
    deferred = np.asarray([int(m.num_deferred) for m in log])
    say(phase="window", ticks=ticks, wall_s=rep.wall_s,
        commits=int(committed.sum()), arrivals=int(window_trace.sum()),
        events=int(events.sum()), deferred_max=int(deferred.max()),
        compiles_in_window=counter.compiles - before,
        host_peak_gb=host_peak_gb())
    peak = _peak_bytes()
    from reference import capacity

    ledger = {"arrivals": window_trace, "events": events,
              "committed": committed, "deferred": deferred,
              "pending0": pending0, "capacity": capacity(cell.cfg)[1]}
    del state, round_fn, rec, log
    gc.collect()
    return {"setup_s": setup_s, "wall_s": rep.wall_s, "ticks": ticks,
            "committed": committed, "latency_us": rep.latency_us,
            "peak_bytes": peak, "prog_metrics": prog_metrics,
            "prog_state": prog_state, "arrivals": trace[:check],
            "ledger": ledger}


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats["peak_bytes_in_use"])


def reference_readings(cell, problem, seed, arrivals, rounds, *,
                       dtype=None, precision="highest", fault=None,
                       hints=None):
    """The reference's first rounds from the seed, as host arrays;
    ``hints``: the compared run's events per round (see
    ``reference.undecided``)."""
    import jax
    import jax.numpy as jnp

    from reference import init_state, make_round

    dtype = jnp.float32 if dtype is None else dtype
    st = init_state(cell.cfg, problem.params0, seed, dtype)
    step = make_round(cell.model, cell.cfg, problem.data, problem.layout,
                      problem.params0, dtype=dtype, precision=precision,
                      fault=fault)
    metrics = []
    for r in range(rounds):
        arr = None if arrivals is None else jnp.asarray(arrivals[r])
        hint = None if hints is None else jnp.asarray(hints[r])
        st, m = step(st, arr, hint)
        metrics.append({k: np.asarray(jax.device_get(v))
                        for k, v in m.items()})
    state = {k: np.asarray(jax.device_get(st[k]).astype(np.float32))
             for k in ("theta", "lam", "z", "omega")}
    return metrics, state


def control(cell) -> dict:
    """The control's arguments to :func:`compare`: the reference one
    precision below what the configuration states."""
    import jax.numpy as jnp

    if cell.cfg["matmul_precision"] == "highest":
        return {"precision": "high"}
    return {"dtype": jnp.bfloat16}


def compare(cell, problem, seed, prog_metrics, prog_state, arrivals, *,
            dtype=None, precision=None, fault=None,
            diagnostics: bool = False) -> dict:
    """The numbers of the comparison; ``diagnostics`` adds
    ``row_gap_median``, the median of :func:`check.row_gaps`, and both
    sides' largest round loss and row norm (no limit: calibration
    only)."""
    from check import round_gaps, row_gaps, row_norms, state_gap

    rounds = len(prog_metrics)
    if dtype is not None or precision is not None or fault is not None:
        # The control or a planted fault in the program's place.
        prog_metrics, prog_state = reference_readings(
            cell, problem, seed, arrivals, rounds, dtype=dtype,
            precision=precision or "highest", fault=fault)
    ref_metrics, ref_state = reference_readings(
        cell, problem, seed, arrivals, rounds,
        hints=[m["events"] for m in prog_metrics])
    start = _start_state(cell, problem)
    nums = round_gaps(prog_metrics, ref_metrics, cell.cfg["delta0"])
    nums["state_gap"] = state_gap(prog_state, ref_state, start)
    if diagnostics:
        gaps = row_gaps(prog_state, ref_state, start)
        nums["row_gap_median"] = float(np.median(gaps)) if gaps.size \
            else 0.0
        for side, ms, st in (("prog", prog_metrics, prog_state),
                             ("ref", ref_metrics, ref_state)):
            nums[side + "_loss_max"] = float(np.max(
                [m["train_loss"] for m in ms]))
            nums[side + "_theta_norm_max"] = float(np.max(
                row_norms(st["theta"])))
    return nums


def _start_state(cell, problem) -> dict:
    from jax.flatten_util import ravel_pytree

    flat = np.asarray(ravel_pytree(problem.params0)[0], np.float64)
    n = cell.cfg["n_clients"]
    rows = np.broadcast_to(flat, (n, flat.shape[0]))
    return {"theta": rows, "lam": np.broadcast_to(0.0, rows.shape),
            "z": rows, "omega": flat}


def _finish(cell, problem, seed, trace, devices, tracer, out) -> dict:
    from check import judge, ledger_faults

    t = time.perf_counter()
    nums = compare(cell, problem, seed, out["prog_metrics"],
                   out["prog_state"], out["arrivals"])
    if out["ledger"] is not None:
        nums["ledger_faults"] = ledger_faults(**out["ledger"])
    say(phase="check", check_s=time.perf_counter() - t,
        host_peak_gb=host_peak_gb())
    correct, table = judge(nums, cell.limits)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["peak_bytes"]}
    result = {"correct": correct,
              "attempted": int(out.get("rounds", out.get("ticks", 0))),
              "failed": 0 if correct else 1}
    if trace:
        from metrics_ctx import per_layer

        metrics, extra = per_layer(cell, problem, dev, tracer, out)
        device.update(extra.pop("device"))
        result["metrics"] = metrics
        result["device"] = device
        result.update(extra)
    else:
        result["metrics"] = end_to_end(cell, out)
        result["device"] = device
    result["checks"] = table
    return result


def end_to_end(cell, out) -> dict:
    values = {"setup_s": out["setup_s"]}
    if out["peak_bytes"] is not None:
        values["peak_hbm_gb"] = out["peak_bytes"] / 1e9
    if "rounds" in out:
        values["rounds_per_s"] = out["rounds"] / out["wall_s"]
    else:
        values["commits_per_s"] = float(out["committed"].sum()) \
            / out["wall_s"]
        lat = out["latency_us"]
        if lat.size:
            values["commit_latency_p95_ms"] = float(
                np.percentile(lat, 95)) / 1e3
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
