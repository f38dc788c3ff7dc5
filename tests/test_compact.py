"""Capacity-bounded compaction: plan mechanics, dense-path parity
(capacity=N ⇒ bit-identical events, fp32-tolerance state, with the
deferral queue enabled, across {1,2}-device meshes × {flat, pytree}
layouts × kernel forms), queue carry + adaptive capacity behavior,
overflow deferral, and the fused-round op-count assertions (--runslow).
Quantified invariants live in tests/test_compact_properties.py."""
import dataclasses
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
from repro.core.compact import capacity_for, compact_plan
from repro.core.engine import participant_mean
from repro.data import make_least_squares

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(n, **kw):
    base = dict(algorithm="fedback", n_clients=n, participation=0.5,
                rho=1.0, lr=0.1, momentum=0.0, epochs=2, batch_size=4,
                controller=ControllerConfig(K=0.2, alpha=0.9))
    base.update(kw)
    return FLConfig(**base)


class TestCompactPlan:
    def test_prioritizes_largest_distances(self):
        events = jnp.asarray([True, True, False, True, True])
        dist = jnp.asarray([0.1, 0.9, 5.0, 0.5, 0.3])
        plan = compact_plan(events, dist, capacity=2)
        # stalest fired clients: 1 (0.9) then 3 (0.5); client 2 did not fire
        np.testing.assert_array_equal(np.asarray(plan.idx), [1, 3])
        assert np.asarray(plan.valid).all()
        np.testing.assert_array_equal(
            np.asarray(plan.committed), [False, True, False, True, False])
        assert int(plan.num_deferred) == 2

    def test_capacity_exceeds_fired(self):
        events = jnp.asarray([False, True, False, False])
        dist = jnp.ones((4,))
        plan = compact_plan(events, dist, capacity=3)
        np.testing.assert_array_equal(np.asarray(plan.valid),
                                      [True, False, False])
        assert int(plan.num_deferred) == 0
        np.testing.assert_array_equal(np.asarray(plan.committed), events)

    def test_tie_break_is_deterministic_low_index_first(self):
        events = jnp.ones((4,), bool)
        plan = compact_plan(events, jnp.zeros((4,)), capacity=2)
        np.testing.assert_array_equal(np.asarray(plan.idx), [0, 1])

    def test_capacity_for(self):
        assert capacity_for(100, 0.25, 1.5) == 38  # ceil(37.5)
        assert capacity_for(100, 0.25, 1.5, capacity=100) == 100
        assert capacity_for(100, 1.0, 2.0) == 100  # clamped to N
        assert capacity_for(8, 0.25, 1.5, n_shards=2) == 2  # ceil(3/2)
        assert capacity_for(4, 0.0, 1.5) == 1  # floor of one row

    def test_capacity_for_per_shard_rounds_up(self):
        """Regression: C_global=5 over 4 shards must give ⌈5/4⌉=2 per
        shard (a floor split would lose the remainder client)."""
        assert capacity_for(16, 0.3, 1.0, n_shards=4) == 2
        # global sum always covers the budget (up to the N ceiling)
        for n, rate, slack, shards in [(16, 0.3, 1.0, 4), (12, 0.5, 1.1, 3),
                                       (64, 0.17, 1.3, 8), (6, 0.9, 2.0, 2)]:
            import math
            c_global = math.ceil(slack * rate * n)
            per = capacity_for(n, rate, slack, n_shards=shards)
            assert per * shards >= min(c_global, n), (n, rate, slack, shards)

    def test_capacity_for_rejects_uneven_shards(self):
        with pytest.raises(ValueError):
            capacity_for(10, 0.5, 1.0, n_shards=3)

    def test_capacity_bounds(self):
        from repro.core.compact import capacity_bounds
        c_min, c_max = capacity_bounds(100, 0.25, 1.5)
        assert (c_min, c_max) == (25, 38)
        # explicit budget pins both views of the ceiling
        assert capacity_bounds(100, 0.25, 1.5, capacity=30)[1] == 30
        # tightest slack collapses the interval
        c_min, c_max = capacity_bounds(16, 0.25, 1.0)
        assert c_min == c_max == 4

    def test_queue_priority_age_beats_distance(self):
        """A deferred client outranks every fresh fire even with the
        smallest trigger distance (starvation-free ordering)."""
        events = jnp.asarray([True, True, True, True])
        dist = jnp.asarray([9.0, 8.0, 7.0, 0.01])
        age = jnp.asarray([0, 0, 1, 2], jnp.int32)
        plan = compact_plan(events, dist, capacity=2, age=age)
        np.testing.assert_array_equal(np.asarray(plan.idx), [3, 2])

    def test_limit_caps_commits_below_capacity(self):
        events = jnp.ones((6,), bool)
        plan = compact_plan(events, jnp.arange(6, 0, -1.0), capacity=4,
                            limit=2)
        assert int(np.asarray(plan.committed).sum()) == 2
        assert int(np.asarray(plan.valid).sum()) == 2
        assert int(plan.num_deferred) == 4


class TestCompactParity:
    @pytest.mark.parametrize("algorithm", ["fedback", "fedavg"])
    def test_capacity_n_matches_dense(self, algorithm):
        n = 8
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        kw = dict(rho=0.0) if algorithm == "fedavg" else {}
        dense = _cfg(n, algorithm=algorithm, **kw)
        compact = dataclasses.replace(dense, compact=True, capacity=n)

        def run(cfg):
            state = init_state(cfg, params0, spec=spec)
            round_fn = make_round_fn(cfg, ls, data, spec=spec)
            events = []
            for _ in range(10):
                state, m = round_fn(state)
                events.append(np.asarray(m.events).astype(int).tolist())
                assert int(m.num_deferred) == 0
            return state, events

        st_d, ev_d = run(dense)
        st_c, ev_c = run(compact)
        assert ev_d == ev_c  # bit-identical event decisions
        for name in ("theta", "lam", "z_prev", "omega"):
            np.testing.assert_allclose(
                np.asarray(getattr(st_c, name)),
                np.asarray(getattr(st_d, name)), rtol=1e-6, atol=1e-7,
                err_msg=name)

    def test_capacity_n_matches_dense_tree_layout(self):
        n = 6
        data, params0, ls = make_least_squares(n, 8, 5)
        dense = _cfg(n)
        compact = dataclasses.replace(dense, compact=True, capacity=n)

        def run(cfg):
            state = init_state(cfg, params0)
            round_fn = make_round_fn(cfg, ls, data)
            for _ in range(8):
                state, m = round_fn(state)
            return state

        st_d, st_c = run(dense), run(compact)
        np.testing.assert_allclose(np.asarray(st_c.omega["theta"]),
                                   np.asarray(st_d.omega["theta"]),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("layout,kernel", [
        ("flat", False), ("flat", True), ("tree", False)])
    def test_parity_matrix_single_device(self, layout, kernel):
        """capacity=N compact vs dense, queue enabled: bit-identical
        events, fp32-tolerant ω — {flat, pytree} layouts × {reference,
        fused-kernel} ADMM forms (the kernel form needs the flat
        layout; the 2-device leg of the matrix runs in
        TestCompactShardedParity)."""
        n = 8
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0) if layout == "flat" else None
        dense = _cfg(n, use_admm_kernel=kernel, use_trigger_kernel=kernel)
        compact = dataclasses.replace(dense, compact=True, capacity=n)

        def run(cfg):
            state = init_state(cfg, params0, spec=spec)
            round_fn = make_round_fn(cfg, ls, data, spec=spec)
            events = []
            for _ in range(8):
                state, m = round_fn(state)
                events.append(np.asarray(m.events).astype(int).tolist())
                assert int(m.num_deferred) == 0
                assert np.asarray(state.queue.age).max() == 0
            return state, events

        st_d, ev_d = run(dense)
        st_c, ev_c = run(compact)
        assert ev_d == ev_c
        omega_d = (st_d.omega if layout == "flat" else st_d.omega["theta"])
        omega_c = (st_c.omega if layout == "flat" else st_c.omega["theta"])
        np.testing.assert_allclose(np.asarray(omega_c),
                                   np.asarray(omega_d),
                                   rtol=1e-6, atol=1e-7)

    def test_kernel_with_z_forms_agree(self):
        """The two fused kernel forms used by the round engines agree
        bit-wise on λ⁺/center, and the with_z=False form's post-solve z
        assembly matches the with_z=True kernel output."""
        from repro.kernels import ops
        rng = np.random.default_rng(0)
        theta = jnp.asarray(rng.standard_normal((8, 33)), jnp.float32)
        lam = jnp.asarray(rng.standard_normal((8, 33)), jnp.float32)
        omega = jnp.asarray(rng.standard_normal((33,)), jnp.float32)
        lam3, z3, c3 = ops.admm_update(theta, lam, omega, with_z=True)
        lam2, c2 = ops.admm_update(theta, lam, omega, with_z=False)
        np.testing.assert_array_equal(np.asarray(lam3), np.asarray(lam2))
        np.testing.assert_array_equal(np.asarray(c3), np.asarray(c2))
        np.testing.assert_array_equal(np.asarray(z3),
                                      np.asarray(theta + lam2))


class TestOverflowDeferral:
    def test_round_zero_overflow_defers_and_keeps_state(self):
        """δ⁰=0 fires all N; with capacity C < N exactly C commit and
        the deferred clients' state is untouched."""
        n, cap = 8, 3
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(n, compact=True, capacity=cap)
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        th0 = np.asarray(state.theta)
        state2, m = round_fn(state)
        assert int(m.num_events) == n
        assert int(m.num_deferred) == n - cap
        changed = np.abs(np.asarray(state2.theta) - th0).max(axis=1) > 0
        assert int(changed.sum()) == cap

    def test_deferral_is_transient_under_controller(self):
        """Once the controller throttles toward L̄, firing mostly fits
        the slack capacity: deferral collapses from the round-0 burst
        (N − C clients) to a small oscillation residual."""
        n = 16
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(n, participation=0.25, compact=True, capacity_slack=1.5,
                   controller=ControllerConfig(K=0.5, alpha=0.9))
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        state, hist = run_rounds(round_fn, state, 30)
        deferred = np.asarray(hist.num_deferred)
        cap = capacity_for(n, 0.25, 1.5)
        assert deferred[0] == n - cap  # round 0 fires everyone
        assert deferred[-10:].mean() < 1.0  # throttled into capacity


class TestDeferralCarry:
    def test_carried_client_served_without_refiring(self):
        """A deferred client is carried into the next plan by the queue:
        it gets served even when its trigger stays quiet (no re-fire)."""
        n, cap = 8, 2
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(n, compact=True, capacity=cap)
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        state, m = round_fn(state)  # δ⁰=0: all fire, cap commit
        assert int(m.num_deferred) == n - cap
        pending = np.asarray(state.queue.age) > 0
        # mute every trigger: no fresh event can fire next round
        state = state._replace(ctrl=state.ctrl._replace(
            delta=jnp.full((n,), 1e9, jnp.float32)))
        th_before = np.asarray(state.theta)
        state, m = round_fn(state)
        assert int(m.num_events) == 0  # nothing fired...
        changed = np.abs(np.asarray(state.theta) - th_before).max(axis=1) > 0
        assert int(changed.sum()) == cap  # ...yet cap carried rows served
        assert np.all(pending[changed])  # exactly from the queue
        assert int(m.num_deferred) == n - 2 * cap

    def test_queue_drains_oldest_first(self):
        """Round-robin service of the round-0 burst: every client is
        served exactly once within ⌈N/C⌉ rounds at an explicit budget."""
        n, cap = 8, 2
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(n, compact=True, capacity=cap)
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        th0 = np.asarray(state.theta)
        served_total = np.zeros(n, bool)
        for _ in range(n // cap):  # ⌈N/C⌉ rounds
            state, m = round_fn(state)
            # mute fresh triggers so only the burst queue is in play
            state = state._replace(ctrl=state.ctrl._replace(
                delta=jnp.full((n,), 1e9, jnp.float32)))
        served_total = np.abs(np.asarray(state.theta) - th0).max(axis=1) > 0
        assert served_total.all()  # the whole burst served, none starved
        assert int(m.num_deferred) == 0
        assert np.asarray(state.queue.age).max() == 0


class TestAdaptiveCapacity:
    def test_realized_capacity_within_bounds_and_adapts(self):
        from repro.core.compact import capacity_bounds
        n = 16
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(n, participation=0.25, compact=True, capacity_slack=2.0,
                   controller=ControllerConfig(K=0.5, alpha=0.9))
        c_min, c_max = capacity_bounds(n, 0.25, 2.0)
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        state, hist = run_rounds(round_fn, state, 40)
        caps = np.asarray(hist.realized_capacity)
        slacks = np.asarray(hist.realized_slack)
        assert np.all((caps >= c_min) & (caps <= c_max))
        assert caps[0] == c_max  # δ⁰=0 burst predicted by the load init
        assert caps.min() < c_max  # throttles once demand subsides
        np.testing.assert_allclose(slacks, caps / (0.25 * n), rtol=1e-6)

    def test_explicit_budget_pins_the_limit(self):
        n, cap = 8, 3
        data, params0, ls = make_least_squares(n, 8, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(n, compact=True, capacity=cap)
        state = init_state(cfg, params0, spec=spec)
        round_fn = make_round_fn(cfg, ls, data, spec=spec)
        state, hist = run_rounds(round_fn, state, 6)
        np.testing.assert_array_equal(np.asarray(hist.realized_capacity),
                                      cap)

    def test_dense_reports_full_capacity(self):
        n = 6
        data, params0, ls = make_least_squares(n, 8, 5)
        cfg = _cfg(n)
        state = init_state(cfg, params0)
        round_fn = make_round_fn(cfg, ls, data)
        state, m = round_fn(state)
        assert int(m.realized_capacity) == n
        assert float(m.realized_slack) == pytest.approx(n / (0.5 * n))


class TestRunRoundsDriver:
    def test_metrics_stay_on_device_and_stack(self, monkeypatch):
        n = 4
        data, params0, ls = make_least_squares(n, 8, 5)
        cfg = _cfg(n)
        state = init_state(cfg, params0)
        round_fn = make_round_fn(cfg, ls, data)
        calls, device_get = [], jax.device_get

        def counted_round(st):
            calls.append("round")
            return round_fn(st)

        def counted_get(tree):
            calls.append("fetch")
            return device_get(tree)

        monkeypatch.setattr(jax, "device_get", counted_get)
        state2, hist = run_rounds(counted_round, state, 5)
        monkeypatch.undo()
        # No host fetch inside the loop: one fetch, of the stacked
        # metrics, after the last round.
        assert calls == ["round"] * 5 + ["fetch"]
        assert isinstance(hist.events, np.ndarray)
        assert hist.events.shape == (5, n)
        assert hist.num_events.shape == (5,)
        # matches a manual python loop driving the same program
        state3, evs = init_state(cfg, params0), []
        for _ in range(5):
            state3, m = round_fn(state3)
            evs.append(np.asarray(m.events))
        np.testing.assert_array_equal(np.asarray(hist.events),
                                      np.stack(evs))


class TestParticipantMeanDtype:
    def test_bf16_leaves_stay_bf16(self):
        events = jnp.asarray([True, False, True])
        per_client = {"w": jnp.ones((3, 4), jnp.bfloat16)}
        fallback = {"w": jnp.zeros((4,), jnp.bfloat16)}
        out = participant_mean(per_client, events, fallback)
        assert out["w"].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out["w"], np.float32), 1.0)

    def test_fp32_unchanged(self):
        events = jnp.asarray([True, True])
        per_client = {"w": jnp.asarray([[2.0], [4.0]], jnp.float32)}
        fallback = {"w": jnp.zeros((1,), jnp.float32)}
        out = participant_mean(per_client, events, fallback)
        assert out["w"].dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(out["w"]), [3.0])


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses, json
import jax, numpy as np
from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn
from repro.data import make_least_squares
from repro.sharding.clients import make_client_mesh

N = 8
data, p0, ls = make_least_squares(N, 8, 5)
spec = make_flat_spec(p0)
base = FLConfig(algorithm="fedback", n_clients=N, participation=0.5,
                rho=1.0, lr=0.1, momentum=0.0, epochs=2, batch_size=4,
                controller=ControllerConfig(K=0.2, alpha=0.9))
kernel = dataclasses.replace(base, use_trigger_kernel=True,
                             use_admm_kernel=True)
variants = {"flat": (base, spec), "tree": (base, None),
            "kernel": (kernel, spec)}
mesh = make_client_mesh(2)
out = {}
for vname, (vcfg, vspec) in variants.items():
    ccfg = dataclasses.replace(vcfg, compact=True, capacity=N)
    for tag, c, m in (("dense_single", vcfg, None),
                      ("compact_sharded", ccfg, mesh)):
        state = init_state(c, p0, spec=vspec, mesh=m)
        round_fn = make_round_fn(c, ls, data, spec=vspec, mesh=m)
        events, deferred = [], 0
        for _ in range(10):
            state, met = round_fn(state)
            events.append(np.asarray(met.events).astype(int).tolist())
            deferred += int(met.num_deferred)
        w = np.concatenate([np.asarray(l, np.float64).ravel()
                            for l in jax.tree.leaves(state.omega)])
        th = jax.tree.leaves(state.theta)[0]
        age = jax.tree.leaves(state.queue.age)[0]
        out[f"{vname}/{tag}"] = {
            "events": events, "omega": w.tolist(), "deferred": deferred,
            "sharding": str(th.sharding),
            "queue_sharding": str(age.sharding)}
print("RESULT:" + json.dumps(out))
"""


class TestCompactShardedParity:
    """2-device legs of the parity matrix: {flat, tree, kernel} compact
    sharded runs vs their single-device dense references — queue
    enabled, capacity=N (nothing may defer)."""

    VARIANTS = ("flat", "tree", "kernel")

    @pytest.fixture(scope="class")
    def result(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=560,
                             cwd=REPO)
        assert out.returncode == 0, out.stderr[-3000:]
        line = [l for l in out.stdout.splitlines()
                if l.startswith("RESULT:")]
        return json.loads(line[-1][len("RESULT:"):])

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_state_and_queue_are_client_sharded(self, result, variant):
        r = result[f"{variant}/compact_sharded"]
        assert "clients" in r["sharding"]
        assert "clients" in r["queue_sharding"]

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_events_bit_identical_to_single_device_dense(self, result,
                                                         variant):
        assert (result[f"{variant}/dense_single"]["events"]
                == result[f"{variant}/compact_sharded"]["events"])

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_omega_within_fp32_tolerance(self, result, variant):
        a = np.asarray(result[f"{variant}/dense_single"]["omega"])
        b = np.asarray(result[f"{variant}/compact_sharded"]["omega"])
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_capacity_n_never_defers(self, result, variant):
        assert result[f"{variant}/compact_sharded"]["deferred"] == 0


# The fused-round op-count assertions (exactly one Pallas ADMM pass,
# no surviving full-width sweeps, tree layout kernel-free) moved onto
# the repro.analysis rule engine -- tests/test_analysis.py runs them
# in tier-1 over a fast configuration subset, and the tracecheck CLI
# gates the full matrix nightly.  See docs/analysis.md.
