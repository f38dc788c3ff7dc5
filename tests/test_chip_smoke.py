"""CPU rehearsal of ``chip_smoke.py`` and of the helpers it shares.

The smoke script drives the main path on a TPU; here its phase
functions run end to end at a tiny size, with the Pallas kernels in
interpret mode, so a wrong path, argument or check shows up before any
chip time is spent.  ``main()`` itself must refuse this backend.
"""
import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from repro.launch.roofline import MODEL_KIND, PEAKS, peaks
from repro.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(n_clients=16)


def test_phases_rehearse_on_cpu(chip_smoke, capsys):
    problem = chip_smoke.build_problem(seed=0, n_train=1600, n_test=200,
                                       hidden=8, **TINY)
    compiled = chip_smoke.phase_compile(problem, seed=0, require_kernels=False,
                                **TINY)
    ref = chip_smoke.phase_parity(problem, compiled, seed=0, rounds=2, **TINY)
    chip_smoke.phase_host(problem, ref["events"], seed=0, rounds=2, **TINY)
    chip_smoke.phase_serve(problem, seed=0, ticks=6, **TINY)
    lines = capsys.readouterr().out.splitlines()
    phases = [ln.split()[0] for ln in lines]
    assert phases == ["phase=compile"] * 2 + ["phase=parity"] * 2 + [
        "phase=host", "phase=serve"]


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import chip_smoke as cs
cs._import_repro()
problem = cs.build_problem(seed=0, n_clients=16, n_train=1600, n_test=200,
                           hidden=8)
cs.phase_mesh(problem, seed=0, n_devices=4, n_clients=16, rounds=3)
"""


def test_mesh_phase_rehearses_on_four_cpu_devices():
    # The mesh needs forced host devices before JAX starts: a child.
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = out.stdout.splitlines()[-1]
    assert line.startswith("phase=mesh devices=4 rounds=3 "
                           "theta_devices=[4, 4, 4]"), line


def test_main_refuses_a_backend_without_tpu(chip_smoke, capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.main([])
    out = capsys.readouterr().out
    assert not any(ln.startswith("{") for ln in out.splitlines())


def test_kernel_calls_reads_op_metadata(chip_smoke):
    def call(op_name):
        return ('  %k = f32[8] custom-call(%a), custom_call_target='
                f'"tpu_custom_call", metadata={{op_name="{op_name}"}}')

    text = "\n".join([
        call("jit(round_fn)/jit(trigger_sq_norms)/pallas_call"),
        call("jit(round_fn)/shard_map/jit(fused_gss)/pallas_call"),
        '  %f = f32[8] fusion(%a), metadata={op_name="jit(admm_update)/"}',
    ])
    assert chip_smoke.kernel_calls(text) == {"trigger_sq_norms": 1,
                                     "admm_update": 0, "fused_gss": 1}


def test_smoke_config_is_the_paper_mnist_deployment(chip_smoke):
    cfg = chip_smoke.smoke_config(seed=3, kernels=True)
    assert (cfg.n_clients, cfg.participation, cfg.rho, cfg.epochs,
            cfg.batch_size) == (100, 0.1, 0.01, 2, 42)
    assert (cfg.controller.K, cfg.controller.alpha) == (2.0, 0.9)
    assert cfg.compact and cfg.fused_gss and cfg.use_admm_kernel \
        and cfg.use_trigger_kernel and cfg.seed == 3


class TestCompileCache:
    def test_env_var_wins_and_nothing_is_set(self, monkeypatch):
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_in_repo_dir_otherwise(self, monkeypatch):
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = compile_cache.enable_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


class TestPeaks:
    def test_v5e_entry(self):
        v5e = peaks("TPU v5 lite")
        assert MODEL_KIND == "TPU v5 lite"
        assert (v5e["bf16_flops"], v5e["hbm_bw"]) == (197e12, 819e9)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
    def test_unknown_kind_raises(self, kind):
        assert kind not in PEAKS
        with pytest.raises(ValueError, match="no published peaks"):
            peaks(kind)


def test_last_line_contract_shape(chip_smoke, monkeypatch, capsys):
    """With every phase stubbed, main's last line is the JSON contract."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    problem = types.SimpleNamespace(data={"x": np.zeros((2, 3))},
                                    spec=types.SimpleNamespace(dim=3))
    monkeypatch.setattr(chip_smoke, "phase_device", lambda: device)
    monkeypatch.setattr(chip_smoke, "build_problem", lambda **kw: problem)
    for name in ("phase_compile", "phase_host", "phase_serve"):
        monkeypatch.setattr(chip_smoke, name, lambda *a, **k: {})
    monkeypatch.setattr(chip_smoke, "phase_parity",
                        lambda *a, **k: {"events": 0})
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "/cache")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": device}
