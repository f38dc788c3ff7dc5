"""The benchmark's own pieces: every cell resolves by name, the trace
reduction, the byte and FLOP counts, the ledger, the host check's blocks,
the entry's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

import harness
from conftest import BENCH, SMALL

ROOT = os.path.dirname(BENCH)
BM = harness.benchmark()
CELLS = [w["name"] for w in BM["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.resolve(cell)
    assert c.cfg["name"] == c.name.split(".")[0] or c.cfg["name"]
    assert c.traffic["kind"] in ("rounds", "serve")
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]).read)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    assert set(c.limits) >= {"events_diff", "committed_diff", "dist_gap",
                             "state_gap"}


def test_benchmark_json_shape():
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    cfg_names = {c["name"] for c in BM["configs"]}
    for c in BM["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] \
            == c["name"]
        assert any(w["config"] == c["name"] for w in BM["workloads"])
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["config"] in cfg_names and w["chips"] == 1
               for w in BM["workloads"])
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in CELLS
            mv = e2e[m["moves"]]
            assert "workloads" not in mv or w in mv["workloads"]


@pytest.mark.parametrize("name,dim,flops", [
    ("paper_mnist_mlp", 159_010, 317_600),
    ("paper_cifar_cnn", 196_426, 16_205_056),
])
def test_widths_and_flops(name, dim, flops):
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    model = harness.load_module(os.path.join(BENCH, "configs", name + ".py"),
                                "m_" + name)
    shapes = jax.eval_shape(lambda k: model.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == dim
    assert model.forward_flops(cfg) == flops


def test_kernel_byte_counts():
    trig = harness.metric_reader("trigger_sq_norms_roofline")
    gss = harness.metric_reader("fused_gss_roofline")
    n, c, d = 100, 16, 159_010
    assert trig.hbm_bytes(n, d) == (100 * 159_010 + 159_010 + 100) * 4
    assert gss.hbm_bytes(c, 10, d) == (3 * 16 + 3 * 10 + 1) * 159_010 * 4


def test_round_mfu_counts_committed_work():
    from metrics_ctx import Context

    mfu = harness.metric_reader("round_mfu")
    committed = np.zeros((2, 3), bool)
    committed[0, 0] = committed[1, 2] = True
    ctx = Context(kind="rounds", cfg={"epochs": 2, "batch_size": 42},
                  n_clients=3, dim=1, capacity=2, wall_s=2.0, steps=2,
                  committed=committed, sizes=np.array([600, 600, 100]),
                  flops_per_example=10, peaks={"bf16_flops": 1e4},
                  trace=None)
    # 2 epochs x 14 batches x 42 + 2 epochs x 2 batches x 42, 3 passes.
    flops = (2 * 14 * 42 + 2 * 2 * 42) * 3 * 10
    assert mfu.solve_flops(ctx) == flops
    assert mfu.read(ctx) == pytest.approx(100 * flops / 2.0 / 1e4)


def _whole_matrix_row_gaps(prog, ref, start):
    """``check.row_gaps`` with every matrix cast to float64 at once."""
    gaps = []
    for key in ("theta", "lam", "z"):
        p = prog[key].astype(np.float64)
        r = ref[key].astype(np.float64)
        moved = np.linalg.norm(r - start[key], axis=1)
        scale = float(np.median(moved[moved > 0])) if np.any(moved > 0) \
            else 1e-30
        gap = np.linalg.norm(p - r, axis=1) / np.maximum(moved, scale)
        gaps.append(gap[(moved > 0) | (gap != 0)])
    return np.concatenate(gaps)


@pytest.mark.parametrize("rows", [1, 7, 64, 1_000])
def test_blocked_state_check_equals_whole_matrix(rows, monkeypatch):
    import check
    from check import row_gaps, row_norms, state_gap

    monkeypatch.setattr(check, "BLOCK_ROWS", rows)

    rng = np.random.default_rng(11)
    n, d = 64, 37
    x0 = rng.normal(size=d)
    start = {"theta": np.broadcast_to(x0, (n, d)),
             "lam": np.zeros((n, d)), "z": np.broadcast_to(x0, (n, d)),
             "omega": x0}
    moved = rng.random(n) < 0.4  # the rest stay where they started
    ref, prog = {}, {}
    for key in ("theta", "lam", "z"):
        r = (start[key] + moved[:, None] * rng.normal(size=(n, d)))
        ref[key] = r.astype(np.float32)
        noise = 1e-3 * rng.normal(size=(n, d)) * (rng.random(n) < 0.5)[
            :, None]
        prog[key] = (ref[key] + noise).astype(np.float32)
    ref["omega"] = ref["z"].mean(axis=0)
    prog["omega"] = prog["z"].mean(axis=0)
    whole = _whole_matrix_row_gaps(prog, ref, start)
    np.testing.assert_array_equal(row_gaps(prog, ref, start), whole)
    gap = state_gap(prog, ref, start)
    om = np.linalg.norm(prog["omega"].astype(np.float64) - ref["omega"]) \
        / np.linalg.norm(ref["omega"].astype(np.float64) - x0)
    assert gap == max(float(np.max(whole)), om)
    np.testing.assert_array_equal(
        row_norms(prog["theta"]),
        np.linalg.norm(prog["theta"].astype(np.float64), axis=1))


HAND_TRACE = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000
             stats { metadata_id: 1
                     str_value: "jit(round_fn)/jit(trigger_sq_norms)" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000
             stats { metadata_id: 1
                     str_value: "jit(round_fn)/jit(trigger_sq_norms)/pad" } }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000
             stats { metadata_id: 1
                     str_value: "jit(round_fn)/jit(fused_gss)" } }
    events { metadata_id: 4 offset_ps: 7000000 duration_ps: 5000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "custom-call.1" } }
  event_metadata { key: 2 value { id: 2 name: "pad.3" } }
  event_metadata { key: 3 value { id: 3 name: "custom-call.2" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.9" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines {
    id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 14000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 5000000 }
    events { metadata_id: 3 offset_ps: 5000000 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "run_rounds chunk" } }
  event_metadata { key: 3 value { id: 3 name: "metrics fetch" } }
}
'''


def test_trace_reduction_on_a_hand_built_trace(tmp_path):
    from jax.profiler import ProfileData

    from trace_reduce import reduce_file

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        HAND_TRACE))
    red = reduce_file(str(path))
    # Window 0..14 us; ops cover 1..4 and 6..12 us.
    assert red.window_s == pytest.approx(14e-6)
    assert red.busy_s == pytest.approx(9e-6)
    assert red.kernel("trigger_sq_norms") == (1, pytest.approx(2e-6))
    assert red.kernel("fused_gss") == (1, pytest.approx(2e-6))
    gaps = {(round(s / 1e3), round(e / 1e3), name)
            for s, e, name in red.gaps}
    assert gaps == {(0, 1, "run_rounds chunk"), (4, 6, "run_rounds chunk"),
                    (12, 14, "metrics fetch")}
    br = red.breakdown()
    assert br["device_ops"][0] == ["fusion.9", pytest.approx(5e-6)]
    assert len(br["idle_gaps"]) == 3


def test_ledger_catches_each_broken_rule():
    from check import ledger_faults

    arr = np.array([[1, 1, 0], [0, 1, 1]], bool)
    ev = np.array([[1, 1, 0], [0, 0, 1]], bool)
    cm = np.array([[1, 0, 0], [0, 1, 1]], bool)
    deferred = np.array([1, 0])
    args = dict(pending0=np.zeros(3, bool), capacity=2)
    assert ledger_faults(arr, ev, cm, deferred, **args) == 0
    assert ledger_faults(arr, ev, cm, np.array([0, 0]), **args) == 1
    bad_ev = ev.copy()
    bad_ev[1, 0] = True  # an event without an arrival
    assert ledger_faults(arr, bad_ev, cm, deferred, **args) == 1
    bad_cm = cm.copy()
    bad_cm[0, 2] = True  # a commit without demand
    assert ledger_faults(arr, ev, bad_cm, np.array([1, 0]), **args) >= 1
    assert ledger_faults(arr, ev, cm, deferred, pending0=np.zeros(3, bool),
                         capacity=0) == 2


def test_arrivals_keep_the_load_across_seeds():
    from arrivals import make_arrivals

    spec = harness.resolve("paper_mnist_mlp.serve_bursty").traffic[
        "arrivals"]
    a = make_arrivals(spec, 100, 64, 1)
    b = make_arrivals(spec, 100, 64, 2**31 + 5)
    assert (a.sum(axis=1) == b.sum(axis=1)).all() and (a != b).any()


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_layout_and_capacity_match_the_program(name):
    harness.import_program()
    from repro.core import make_flat_spec
    from repro.core.compact import capacity_bounds

    from reference import capacity

    full_cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    model = harness.load_module(os.path.join(BENCH, "configs", name + ".py"),
                                "m_" + name)
    cfg = dict(full_cfg, **SMALL[name])
    params = model.init_params(jax.random.PRNGKey(3), cfg)
    spec = make_flat_spec(params)
    flat, _ = ravel_pytree(params)
    np.testing.assert_array_equal(np.asarray(spec.flatten(params)),
                                  np.asarray(flat))
    for full in (full_cfg, cfg):
        assert capacity(full) == capacity_bounds(
            full["n_clients"], full["participation"],
            full["capacity_slack"])


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "no TPU" in p.stderr


def test_a_nan_fails_every_gap():
    from check import judge, round_gaps, state_gap

    n = 4
    ok = {"events": np.ones(n, bool), "distances": np.ones(n),
          "delta": np.full(n, -0.2), "committed": np.ones(n, bool),
          "train_loss": np.float32(1.0)}
    bad = dict(ok, distances=np.array([1.0, np.nan, 1.0, 1.0]),
               train_loss=np.float32(np.nan))
    nums = round_gaps([bad], [ok], 0.0)
    assert np.isnan(nums["dist_gap"]) and np.isnan(nums["loss_gap"])
    rows = np.ones((n, 3))
    start = {"theta": rows * 0, "lam": rows * 0, "z": rows * 0,
             "omega": np.zeros(3)}
    ref = {"theta": rows, "lam": rows, "z": rows, "omega": np.ones(3)}
    prog = dict(ref, theta=np.where(np.eye(n, 3) > 0, np.nan, rows))
    assert np.isnan(state_gap(prog, ref, start))
    assert not judge({"dist_gap": float("nan")}, {"dist_gap": 1.0})[0]
