"""Mutation matrix: tracecheck must prove itself by catching seeded
violations with exactly the intended rule.

Each test plants one regression the analyzer exists to prevent — a
stray full-width sweep, a host transfer staged inside the round, a
dropped donation, an f64 leak, a forced retrace, a replicated-state
all-gather — and asserts the rule engine turns *that* rule red while
every other rule stays green.  ``body_transform`` (threaded through
``make_round_fn``) is the seeding hook: it wraps the round body after
construction, so the engine code itself stays untouched.

The cheap mutations trace a jaxpr only and run in tier-1; the
two-device replication mutation and the CLI end-to-end check compile
under a forced multi-device env and are ``--runslow``.
"""
import json
import os
import pathlib
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.artifacts import (
    DEFAULT_DIM,
    DEFAULT_N,
    FAST_MATRIX,
    ConfigKey,
    build_artifact,
)
from repro.analysis.retrace import run_single_trace_check
from repro.analysis.rules import DtypeBan, evaluate

REPO = pathlib.Path(__file__).resolve().parents[1]
DENSE_FLAT = ConfigKey("dense", "flat", "sync", "uniform", 1)
COMPACT_FLAT = ConfigKey("compact", "flat", "sync", "uniform", 1)
HOST_COMPACT = ConfigKey("compact", "flat", "sync", "uniform", 1,
                         "none", "host")


def failing_rules(art):
    return sorted(r.rule for r in evaluate(art) if r.status == "fail")


@pytest.fixture(scope="module")
def compiled_art():
    return build_artifact(DENSE_FLAT)


class TestBaselineGreen:
    def test_unmutated_round_passes_every_rule(self, compiled_art):
        for r in evaluate(compiled_art):
            assert r.status != "fail", (r.rule, r.violations)


class TestSeededMutations:
    def test_stray_full_width_subtraction(self):
        # A no-op (N, D) subtraction on θ before the round — one extra
        # top-level sweep over the sweep budget, and nothing else.
        def extra_sweep(body):
            def wrapped(state, *args, **kw):
                state = state._replace(
                    theta=state.theta - jnp.float32(0.0))
                return body(state, *args, **kw)
            return wrapped

        art = build_artifact(DENSE_FLAT, compile=False,
                             body_transform=extra_sweep)
        assert failing_rules(art) == ["no-full-width-sweeps"]

    def test_host_transfer_staged_in_round(self):
        # jax.device_put of a host scalar inside the traced body — the
        # classic "constant built per round instead of at build time".
        # The explicit target device is what stages the transfer: a
        # placement-free device_put of a host value folds into a
        # constant at trace time.
        def host_staging(body):
            def wrapped(state, *args, **kw):
                state = state._replace(
                    round=state.round + jax.device_put(np.int32(0),
                                                       jax.devices()[0]))
                return body(state, *args, **kw)
            return wrapped

        art = build_artifact(DENSE_FLAT, compile=False,
                             body_transform=host_staging)
        assert failing_rules(art) == ["host-transfer-budget"]

    def test_stray_full_width_transfer_on_host_leg(self):
        # Host-backend leg: stage a full (N, D) device_put inside the
        # streamed solve program — the exact transfer the budget
        # exists to ban (the planned row stream is (C, D) tiles only,
        # never the whole client-state matrix).
        def full_width_leak(solve):
            def wrapped(omega, idx, keys_rows, th_tiles, lam_tiles):
                stray = jax.device_put(
                    np.zeros((DEFAULT_N, DEFAULT_DIM), np.float32),
                    jax.devices()[0])
                return solve(omega + 0.0 * stray[0], idx, keys_rows,
                             th_tiles, lam_tiles)
            return wrapped

        art = build_artifact(HOST_COMPACT, compile=False,
                             body_transform=full_width_leak)
        assert failing_rules(art) == ["host-transfer-budget"]

    def test_unmutated_host_round_green(self):
        # The host leg itself must trace green — its planned row
        # stream (5·C·D·4 B) fits the 8·C·D·4 B budget — or the
        # mutation above proves nothing.
        art = build_artifact(HOST_COMPACT, compile=False)
        assert failing_rules(art) == []

    def test_dropped_admm_kernel(self):
        # Unfusing the ADMM kernel is one mutation, two coupled
        # symptoms: the Pallas-call count drops AND the unfused algebra
        # reintroduces full-width sweeps.  Both rules must fire.
        art = build_artifact(DENSE_FLAT, compile=False,
                             cfg_overrides={"use_admm_kernel": False})
        assert failing_rules(art) == ["fused-admm-pass",
                                      "no-full-width-sweeps"]

    def test_unfused_compact_commit(self):
        # Un-fusing the compacted commit (fused_gss=False) silently
        # reverts to the three-pass gather/z-assembly/scatter dataflow —
        # numerically identical, so only the fused-admm-pass budget can
        # catch it: the compact policy expects exactly one fused-commit
        # pallas_call and zero separate admm passes.
        art = build_artifact(COMPACT_FLAT, compile=False,
                             cfg_overrides={"fused_gss": False})
        assert failing_rules(art) == ["fused-admm-pass"]

    def test_unmutated_fused_compact_round_green(self):
        # The policy default (compact-flat ⇒ fused commit) itself must
        # trace green, or the mutation above proves nothing.
        art = build_artifact(COMPACT_FLAT, compile=False)
        assert failing_rules(art) == []

    def test_f64_leak(self):
        with jax.enable_x64(True):
            j64 = jax.make_jaxpr(lambda x: x * 2.0)(
                jnp.ones((4,), jnp.float64))
        fake = types.SimpleNamespace(
            key=types.SimpleNamespace(name="f64-mutant"),
            jaxpr=j64, compiled_text=None)
        res = DtypeBan().check(fake)
        assert res.status == "fail"
        assert "float64" in res.violations[0]

    def test_dropped_donation(self):
        art = build_artifact(DENSE_FLAT, donate=False)
        assert failing_rules(art) == ["donated-state-aliases"]
        res = {r.rule: r for r in evaluate(art)}["donated-state-aliases"]
        assert res.metrics["aliased_params"] == 0


class TestRetraceSentry:
    def test_value_overrides_do_not_retrace(self):
        res = run_single_trace_check()
        assert res.status == "pass", res.violations
        assert res.metrics["traces"] == 1

    def test_shape_mutation_forces_retrace(self):
        res = run_single_trace_check(shape_mutation=True)
        assert res.status == "fail"
        assert res.metrics["traces"] > 1

    def test_serve_arrival_masks_do_not_retrace(self):
        from repro.analysis.retrace import run_serve_trace_check
        res = run_serve_trace_check()
        assert res.status == "pass", res.violations
        assert res.metrics["traces"] == 1

    def test_serve_aval_mutation_forces_retrace(self):
        from repro.analysis.retrace import run_serve_trace_check
        res = run_serve_trace_check(shape_mutation=True)
        assert res.status == "fail"
        assert res.metrics["traces"] > 1


_REPLICATE_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.artifacts import ConfigKey, build_artifact
from repro.analysis.rules import evaluate
from repro.sharding.clients import make_client_mesh

mesh = make_client_mesh(2)

def replicate_state(body):
    def wrapped(state, *args, **kw):
        state = state._replace(z_prev=jax.lax.with_sharding_constraint(
            state.z_prev, NamedSharding(mesh, P())))
        return body(state, *args, **kw)
    return wrapped

art = build_artifact(ConfigKey("dense", "flat", "sync", "uniform", 2),
                     body_transform=replicate_state)
failing = sorted(r.rule for r in evaluate(art) if r.status == "fail")
print("FAILING=" + ",".join(failing))
"""


def _run(cmd, **kw):
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=900, **kw)


@pytest.mark.slow
class TestMultiDeviceMutations:
    def test_replicated_state_trips_allgather_cap(self):
        # Replicate-instead-of-shard: pinning z_prev to P() makes SPMD
        # all-gather the (N, D) state every round — only the collective
        # budget may fire.
        proc = _run([sys.executable, "-c", _REPLICATE_SCRIPT])
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "FAILING=collective-budget" in proc.stdout


@pytest.mark.slow
class TestCliEndToEnd:
    def test_fast_matrix_gates_clean_against_baseline(self, tmp_path):
        out = tmp_path / "report.json"
        proc = _run([
            sys.executable, "-m", "repro.analysis", "--matrix", "fast",
            "--json", str(out),
            "--baseline", "benchmarks/baselines/ANALYSIS.json"])
        assert proc.returncode == 0, (proc.stdout[-2000:],
                                      proc.stderr[-2000:])
        report = json.loads(out.read_text())
        assert report["lint"]["status"] == "pass"
        assert len(report["configs"]) == len(FAST_MATRIX)
