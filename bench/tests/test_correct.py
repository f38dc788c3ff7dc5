"""``correct`` on whole runs at a small size on the CPU: a sound run
passes; the control (the reference one precision lower, in the
program's place; on the CPU, which has no three-pass float32 mode, the
bfloat16 one for every cell) fails; and a run with the timed path broken underneath
fails, once for each fault a cell can have: a step that returns its
state unchanged, half of each batch left out, an answer altered where
it is produced.  (The exchange between chips does not exist in these
one-chip cells.)"""
import os

import jax.numpy as jnp
import pytest

import harness
from check import judge
from conftest import SMALL, SMALL_TRAFFIC

TRAFFIC = {w["name"]: w["traffic"]
           for w in harness.benchmark()["workloads"]}
CELLS = list(TRAFFIC)
SEED = 2**31 + 17


def run(cell, **kw):
    return harness.run(cell, SEED, 1.0, False, require_tpu=False,
                       overrides=SMALL[cell.split(".")[0]],
                       traffic_overrides=SMALL_TRAFFIC.get(TRAFFIC[cell]), **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    from arrivals import make_arrivals

    c = harness.resolve(cell)
    c.cfg = dict(c.cfg, **SMALL[cell.split(".")[0]])
    harness.setup_jax(False, 1)
    harness.import_program()
    problem = harness.build_problem(c, SEED)
    arrivals = None
    if c.traffic["kind"] == "serve":
        arrivals = make_arrivals(c.traffic["arrivals"], c.cfg["n_clients"],
                                 c.traffic["check_ticks"], SEED)
    steps = c.traffic.get("check_rounds", c.traffic.get("check_ticks"))
    pm, ps = harness.reference_readings(c, problem, SEED, arrivals, steps)
    nums = harness.compare(c, problem, SEED, pm, ps, arrivals,
                           dtype=jnp.bfloat16)
    ok, table = judge(nums, c.limits)
    assert not ok, table


@pytest.mark.parametrize("name", ["paper_mnist_mlp", "paper_cifar_cnn"])
def test_donated_reference_equals_undonated(name, monkeypatch):
    """Flat shards (MNIST) and the pooled layout (CIFAR, a configuration
    with no cell, run under the MNIST sync cell's traffic)."""
    import jax
    import numpy as np

    import reference

    c = harness.resolve("paper_mnist_mlp.sync_rounds")
    c.cfg = dict(harness.load_json(os.path.join(
        harness.BENCH, "configs", name + ".json")), **SMALL[name])
    c.model = harness.load_module(os.path.join(
        harness.BENCH, "configs", name + ".py"), "bench_model_" + name)
    harness.setup_jax(False, 1)
    harness.import_program()
    problem = harness.build_problem(c, SEED)

    def rounds():
        st = reference.init_state(c.cfg, problem.params0, SEED)
        step = reference.make_round(c.model, c.cfg, problem.data,
                                    problem.layout, problem.params0)
        given, metrics = st, []
        for _ in range(c.traffic["check_rounds"]):
            st, m = step(st)
            metrics.append(m)
        return given, jax.device_get((metrics, st))

    given, donated = rounds()
    assert all(given[k].is_deleted() for k in ("theta", "lam", "z"))
    jit = jax.jit
    monkeypatch.setattr(jax, "jit", lambda fn, donate_argnums=(), **kw:
                        jit(fn, **kw))
    given, kept = rounds()
    assert not any(v.is_deleted() for v in jax.tree.leaves(given))
    jax.tree.map(np.testing.assert_array_equal, donated, kept)


def _unchanged(round_fn):
    def broken(state, *args):
        _, metrics = round_fn(state, *args)
        return state, metrics
    return broken


def _altered(round_fn):
    def broken(state, *args):
        state, metrics = round_fn(state, *args)
        theta = state.theta.at[0].add(0.01)
        committed = metrics.committed.at[0].set(~metrics.committed[0])
        return state._replace(theta=theta), metrics._replace(
            committed=committed)
    return broken


def _half_batch_loss(make_loss_fn):
    def make(logits_fn):
        loss = make_loss_fn(logits_fn)

        def half(params, x, y):
            h = max(x.shape[0] // 2, 1)
            return loss(params, x[:h], y[:h])
        return half
    return make


def _half_batch_masked(masked_batch_loss):
    """The pooled clients' loss weighs each example on its own: drop
    the second half of each batch by its weights."""
    def half(loss_fn, params, xb, yb, weights):
        keep = jnp.arange(weights.shape[0]) < max(weights.shape[0] // 2, 1)
        return masked_batch_loss(loss_fn, params, xb, yb,
                                 weights * keep.astype(weights.dtype))
    return half


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    hook = None
    if fault == "state_unchanged":
        hook = _unchanged
    elif fault == "altered_answer":
        hook = _altered
    else:
        harness.import_program()
        import repro.models.mlp as mlp

        import repro.core.fedback as fedback

        monkeypatch.setattr(mlp, "make_loss_fn",
                            _half_batch_loss(mlp.make_loss_fn))
        monkeypatch.setattr(fedback, "masked_batch_loss",
                            _half_batch_masked(fedback.masked_batch_loss))
    out = run(cell, round_hook=hook)
    assert not out["correct"], out["checks"]
    assert out["failed"] == 1
