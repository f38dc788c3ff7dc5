"""The stack of per-round metrics (``run_rounds``, ``run_trace``;
``repro.core.fedback.stack_metrics``).

* the stacked metrics equal the eager per-array ``jnp.stack`` leaf for
  leaf — bit for bit, same dtype and shape — for one round and for more
  than 16 (past ``jnp.concatenate``'s chunking), on the synchronous
  engine (dense and compact), the staleness engine (pipeline fields in
  use) and metrics whose optional fields are ``None``, and through
  ``run_trace``; they come back as host arrays;
* the stack is one program: a second call of a length compiles nothing,
  a new length compiles one program;
* on a 2-device ``clients`` mesh the stacked metrics equal the eager
  stack of the same sharded metrics and match the unsharded run.
"""
import contextlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn, run_rounds
from repro.core.schedule import TraceConfig, make_trace, run_trace
from repro.data import make_least_squares
from repro.utils import pytree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8


def _engine(*, arrivals=False, **kw):
    data, params0, loss = make_least_squares(N, 6, 4)
    spec = make_flat_spec(params0)
    cfg = FLConfig(algorithm="fedback", n_clients=N, participation=0.5,
                   rho=1.0, lr=0.1, momentum=0.0, epochs=1, batch_size=3,
                   controller=ControllerConfig(K=0.2, alpha=0.9), **kw)
    state = init_state(cfg, params0, spec=spec)
    round_fn = make_round_fn(cfg, loss, data, spec=spec,
                             arrivals_arg=arrivals)
    return round_fn, state


def _recording(round_fn):
    """``round_fn`` that keeps each round's metrics as returned."""
    log = []

    def fn(*args):
        state, m = round_fn(*args)
        log.append(m)
        return state, m

    return fn, log


def _eager_stack(history):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *history)


def _assert_bit_identical(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert isinstance(g, np.ndarray)
        assert g.shape == w.shape
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, np.asarray(w))


def _without_optional_fields(round_fn):
    """``round_fn`` whose metrics leave the optional fields ``None``."""

    def fn(state):
        state, m = round_fn(state)
        return state, m._replace(num_inflight=None, num_landed=None,
                                 committed=None)

    return fn


ENGINES = {
    "dense": ({}, None),
    "compact": ({"compact": True}, None),
    "staleness": ({"max_staleness": 2}, None),
    "none_fields": ({}, _without_optional_fields),
}


@pytest.mark.parametrize("rounds", [1, 20])
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_run_rounds_stack_matches_eager(engine, rounds):
    kw, wrap = ENGINES[engine]
    round_fn, state = _engine(**kw)
    fn, log = _recording(wrap(round_fn) if wrap else round_fn)
    state, metrics = run_rounds(fn, state, rounds)
    assert len(log) == rounds
    assert metrics.events.shape == (rounds, N)
    none_fields = engine == "none_fields"
    assert (metrics.num_inflight is None) == none_fields
    assert (metrics.committed is None) == none_fields
    if engine == "staleness" and rounds > 1:
        assert int(metrics.num_inflight.max()) > 0
    _assert_bit_identical(metrics, _eager_stack(log))


def test_run_trace_stack_matches_eager():
    round_fn, state = _engine(arrivals=True)
    trace = make_trace(TraceConfig(kind="bursty", n_clients=N, ticks=18,
                                   rate=0.5, seed=1))
    fn, log = _recording(round_fn)
    state, metrics = run_trace(fn, state, trace)
    assert metrics.committed.shape == (18, N)
    _assert_bit_identical(metrics, _eager_stack(log))


@contextlib.contextmanager
def _backend_compiles():
    """Backend compiles inside the block, counted from the same event
    as the benchmark's ``CompileCounter``."""
    import jax.monitoring as mon

    count = [0]

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    mon.register_event_duration_secs_listener(listener)
    try:
        yield count
    finally:
        mon.unregister_event_duration_listener(listener)


@pytest.fixture
def fresh_compiles():
    """Stack programs compiled anew, not found in a cache."""
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    pytree.tree_stack.clear_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def test_stack_compiles_once_per_length(fresh_compiles):
    round_fn, state = _engine(compact=True)
    state, m = run_rounds(round_fn, state, 7)  # round and stack compiled
    jax.block_until_ready(state)
    with _backend_compiles() as again:
        state, m = run_rounds(round_fn, state, 7)
        jax.block_until_ready(state)
    assert again[0] == 0
    with _backend_compiles() as longer:
        state, m = run_rounds(round_fn, state, 40)
        jax.block_until_ready(state)
    assert longer[0] == 1  # the stack of 40, as one program
    assert m.events.shape == (40, N)


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core import ControllerConfig, FLConfig, init_state, \
    make_round_fn, run_rounds
from repro.data import make_least_squares
from repro.sharding.clients import make_client_mesh

N, R = 8, 18
data, p0, ls = make_least_squares(N, 8, 5)
cfg = FLConfig(algorithm="fedback", n_clients=N, participation=0.5, rho=1.0,
               lr=0.1, momentum=0.0, epochs=2, batch_size=8,
               controller=ControllerConfig(K=0.2, alpha=0.9))
out = {}
for name, mesh in (("single", None), ("sharded", make_client_mesh(2))):
    round_fn = make_round_fn(cfg, ls, data, mesh=mesh)
    log = []

    def fn(s):
        s, m = round_fn(s)
        log.append(m)
        return s, m

    state, metrics = run_rounds(fn, init_state(cfg, p0, mesh=mesh), R)
    eager = jax.tree.map(lambda *xs: jnp.stack(xs), *log)
    out[name] = {
        "leaves": {f: np.asarray(v).tolist()
                   for f, v in metrics._asdict().items() if v is not None},
        "dtypes": {f: str(v.dtype)
                   for f, v in metrics._asdict().items() if v is not None},
        "same_as_eager": all(
            g.shape == w.shape and g.dtype == w.dtype
            and np.array_equal(np.asarray(g), np.asarray(w))
            for g, w in zip(jax.tree.leaves(metrics),
                            jax.tree.leaves(eager))),
        "eager_events_devices": len(eager.events.sharding.device_set),
    }
print("RESULT:" + json.dumps(out))
"""


def test_stack_on_two_device_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    res = json.loads(line[-1][len("RESULT:"):])
    single, sharded = res["single"], res["sharded"]
    assert single["same_as_eager"] and sharded["same_as_eager"]
    assert sharded["eager_events_devices"] == 2  # metrics were sharded
    assert sharded["dtypes"] == single["dtypes"]
    assert len(sharded["leaves"]["events"]) == 18
    # Decisions match the unsharded run exactly; float metrics to fp32
    # tolerance (the consensus all-reduce may reorder the sum).
    for f in ("events", "num_events", "committed", "num_deferred",
              "realized_capacity"):
        assert sharded["leaves"][f] == single["leaves"][f], f
    for f in ("distances", "delta", "load", "train_loss", "realized_slack"):
        np.testing.assert_allclose(sharded["leaves"][f],
                                   single["leaves"][f],
                                   rtol=1e-5, atol=1e-5, err_msg=f)
