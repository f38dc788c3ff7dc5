"""Fused gather→ADMM→scatter commit: parity with the three-pass path.

* kernel level: interpret-mode ``fused_gss`` is bit-identical to the
  jnp ``fused_gss_ref`` oracle — both ``with_z`` forms, partial tiles,
  masked (invalid) lanes, and untouched rows preserved through the
  aliased outputs; the recomputed λ⁺ matches the ``admm_update`` Pallas
  kernel bit for bit (same ``_kernel2``/``_kernel3`` op order);
* round level: the fused compacted engine (``cfg.fused_gss``)
  reproduces the reference gather/z-assembly/scatter engine
  bit-identically — events AND fp32 ω/θ/λ/z_prev — across
  {sync, async} × {uniform, ragged} and, in a forced-2-device
  subprocess, under the client mesh;
* config validation: the fused commit refuses non-compact, non-ADMM
  and tree-layout rounds loudly.

No golden trace is regenerated here: the fused path is opt-in
(``fused_gss=False`` default), so the committed traces must keep
passing byte-identical.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn, pool_data, run_rounds
from repro.data import make_least_squares
from repro.kernels import ops
from repro.kernels.fused_gss import fused_gss, fused_gss_ref

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _problem(n, c, d, seed=0, frac_valid=0.8):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    theta, lam, z = mk(n, d), mk(n, d), mk(n, d)
    omega, solved = mk(d), mk(c, d)
    idx = jnp.asarray(rng.permutation(n)[:c], jnp.int32)
    valid = jnp.asarray(rng.random(c) < frac_valid)
    return idx, valid, solved, omega, theta, lam, z


class TestFusedKernel:
    @pytest.mark.parametrize("n,c,d", [
        (16, 8, 128),    # lane-aligned D
        (64, 24, 256),
        (16, 5, 100),    # D below one 128-lane block
        (8, 3, 7),       # tiny D
        (32, 32, 64),    # every row planned
        (100, 16, 2100),  # partial last row tile and D block
        (13, 13, 300),   # every row of a partial tile planned
    ])
    @pytest.mark.parametrize("with_z", [True, False])
    def test_bit_identical_to_ref(self, n, c, d, with_z):
        idx, valid, solved, omega, theta, lam, z = _problem(n, c, d,
                                                            seed=n + d)
        zarg = z if with_z else None
        got = fused_gss(idx, valid, solved, omega, theta, lam, zarg,
                        interpret=True, with_z=with_z)
        want = fused_gss_ref(idx, valid, solved, omega, theta, lam, zarg,
                             with_z=with_z)
        for g, w in zip(got, want, strict=True):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    def test_unplanned_and_masked_rows_untouched(self):
        idx, valid, solved, omega, theta, lam, z = _problem(
            32, 12, 64, frac_valid=0.5)
        tho, lao, zo = fused_gss(idx, valid, solved, omega, theta, lam, z,
                                 interpret=True)
        committed = set(np.asarray(idx)[np.asarray(valid)].tolist())
        untouched = [r for r in range(32) if r not in committed]
        for out, inp in ((tho, theta), (lao, lam), (zo, z)):
            np.testing.assert_array_equal(
                np.asarray(out)[untouched], np.asarray(inp)[untouched])

    def test_lambda_matches_admm_kernel_bitwise(self):
        # λ⁺ must come out of the same expression the admm_update
        # kernel computes — bit-identical fp32, not merely close.
        idx, valid, solved, omega, theta, lam, z = _problem(
            24, 10, 128, frac_valid=1.0)
        _, lao, _ = fused_gss(idx, valid, solved, omega, theta, lam, z,
                              interpret=True)
        lam_k = ops.admm_update(theta[idx], lam[idx], omega,
                                interpret=True, with_z=False)[0]
        np.testing.assert_array_equal(np.asarray(lao)[np.asarray(idx)],
                                      np.asarray(lam_k))

    def test_z_is_solved_plus_lambda(self):
        idx, valid, solved, omega, theta, lam, z = _problem(
            16, 6, 32, frac_valid=1.0)
        tho, lao, zo = fused_gss(idx, valid, solved, omega, theta, lam, z,
                                 interpret=True)
        rows = np.asarray(idx)
        np.testing.assert_array_equal(np.asarray(tho)[rows],
                                      np.asarray(solved))
        np.testing.assert_array_equal(
            np.asarray(zo)[rows],
            np.asarray(solved + jnp.asarray(lao)[idx]))


def _cfg(n, npts, **kw):
    base = dict(algorithm="fedback", n_clients=n, participation=0.25,
                rho=1.0, lr=0.1, momentum=0.0, epochs=1, batch_size=npts,
                compact=True, capacity_slack=1.5,
                controller=ControllerConfig(K=0.5, alpha=0.9))
    base.update(kw)
    return FLConfig(**base)


def _parity(cfg_a, cfg_b, *, rounds=10, n=32, npts=8, dim=16,
            ragged=False):
    data, params0, loss_fn = make_least_squares(n, npts, dim)
    spec = make_flat_spec(params0)
    rspec = None
    if ragged:
        sizes = [max(npts - 2 * (i % 3), 2) for i in range(n)]
        data, rspec = pool_data(
            [np.asarray(data["x"][i])[:s] for i, s in enumerate(sizes)],
            [np.asarray(data["y"][i])[:s] for i, s in enumerate(sizes)])
    out = []
    for cfg in (cfg_a, cfg_b):
        state = init_state(cfg, params0, spec=spec)
        rf = make_round_fn(cfg, loss_fn, data, spec=spec, ragged=rspec)
        state, hist = run_rounds(rf, state, rounds)
        out.append((state, hist))
    (sa, ha), (sb, hb) = out
    np.testing.assert_array_equal(np.asarray(ha.events),
                                  np.asarray(hb.events))
    for field in ("omega", "theta", "lam", "z_prev"):
        a = np.asarray(getattr(sa, field), np.float32)
        b = np.asarray(getattr(sb, field), np.float32)
        assert a.tobytes() == b.tobytes(), f"{field} not bit-identical"


class TestRoundParity:
    @pytest.mark.parametrize("staleness", [None, 2],
                             ids=["sync", "async"])
    @pytest.mark.parametrize("ragged", [False, True],
                             ids=["uniform", "ragged"])
    def test_fused_matches_reference(self, staleness, ragged):
        _parity(_cfg(32, 8, fused_gss=True, max_staleness=staleness),
                _cfg(32, 8, fused_gss=False, max_staleness=staleness),
                ragged=ragged)

    def test_fused_kernel_matches_fused_jnp(self):
        # The interpret-mode Pallas commit and the jnp fused_gss_ref
        # form of the same round must agree bit for bit too (the trigger
        # kernel runs in both so event decisions share one code path).
        _parity(_cfg(32, 8, fused_gss=True, use_admm_kernel=True,
                     use_trigger_kernel=True),
                _cfg(32, 8, fused_gss=True, use_admm_kernel=False,
                     use_trigger_kernel=True))

    def test_overflow_and_underfill_lanes(self):
        # High target rate + tight slack → rounds that overflow capacity
        # (deferrals) and rounds with invalid plan lanes; the masked
        # write-back must stay bit-exact through both.
        _parity(_cfg(32, 8, participation=0.6, capacity_slack=1.1,
                     fused_gss=True),
                _cfg(32, 8, participation=0.6, capacity_slack=1.1,
                     fused_gss=False), rounds=15)


class TestConfigValidation:
    def test_fused_needs_compact(self):
        data, params0, loss_fn = make_least_squares(8, 4, 5)
        spec = make_flat_spec(params0)
        cfg = _cfg(8, 4, compact=False, fused_gss=True)
        with pytest.raises(ValueError, match="fused_gss"):
            make_round_fn(cfg, loss_fn, data, spec=spec)

    def test_fused_needs_flat_layout(self):
        data, params0, loss_fn = make_least_squares(8, 4, 5)
        cfg = _cfg(8, 4, fused_gss=True)
        with pytest.raises(ValueError, match="fused_gss"):
            make_round_fn(cfg, loss_fn, data)  # tree layout

    def test_fused_needs_admm_family(self):
        from repro.core.compact import make_compact_block
        with pytest.raises(ValueError, match="ADMM"):
            make_compact_block(None, None, 4, is_admm=False,
                               warm_start=False, fused=True)


_TWO_DEVICE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn, pool_data, run_rounds
from repro.data import make_least_squares
from repro.sharding.clients import make_client_mesh

N, NP, D = 32, 8, 16
mesh = make_client_mesh(2)
for ragged in (False, True):
    data, params0, loss_fn = make_least_squares(N, NP, D)
    spec = make_flat_spec(params0)
    rspec = None
    if ragged:
        sizes = [max(NP - 2 * (i % 3), 2) for i in range(N)]
        data, rspec = pool_data(
            [np.asarray(data["x"][i])[:s] for i, s in enumerate(sizes)],
            [np.asarray(data["y"][i])[:s] for i, s in enumerate(sizes)])
    outs = []
    for fused in (True, False):
        cfg = FLConfig(algorithm="fedback", n_clients=N,
                       participation=0.25, rho=1.0, lr=0.1, momentum=0.0,
                       epochs=1, batch_size=NP, compact=True,
                       capacity_slack=1.5, fused_gss=fused,
                       controller=ControllerConfig(K=0.5, alpha=0.9))
        state = init_state(cfg, params0, mesh=mesh, spec=spec)
        rf = make_round_fn(cfg, loss_fn, data, mesh=mesh, spec=spec,
                           ragged=rspec)
        state, hist = run_rounds(rf, state, 10)
        outs.append((state, hist))
    (sa, ha), (sb, hb) = outs
    assert np.array_equal(np.asarray(ha.events), np.asarray(hb.events)), \
        ("events", ragged)
    for f in ("omega", "theta", "lam", "z_prev"):
        a = np.asarray(getattr(sa, f), np.float32).tobytes()
        b = np.asarray(getattr(sb, f), np.float32).tobytes()
        assert a == b, (f, ragged)
print("TWO_DEVICE_PARITY_OK")
"""


class TestTwoDeviceParity:
    def test_fused_matches_reference_under_mesh(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run([sys.executable, "-c", _TWO_DEVICE_SCRIPT],
                              cwd=REPO, env=env, capture_output=True,
                              text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "TWO_DEVICE_PARITY_OK" in proc.stdout
