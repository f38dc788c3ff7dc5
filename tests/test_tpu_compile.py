"""Ahead-of-time compiles for a described TPU v5e chip, no chip needed.

Interpret mode runs every kernel body on the CPU but never meets the
TPU compiler, which refuses blocks below its (8, 128) tile, layouts
that do not match, and more VMEM than a kernel may use.  These tests
hand the installed TPU compiler a described ``v5e:2x2`` topology and
compile, with ``interpret=False``:

* the three main-path Pallas kernels at the widths the benchmark cells
  run — the paper's MNIST round (N = 100 clients, C = 16 solver rows,
  D = 159,010) and the large-population width (N = 65,536, C = 1,967,
  D = 64);
* the whole compacted round at a small size, fused and unfused, to
  show each kernel reaches the compiled module as a
  ``tpu_custom_call`` (the check ``chip_smoke.py`` makes on the chip).

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process may load the TPU library, and
every test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.admm_update import admm_update
from repro.kernels.fused_gss import fused_gss
from repro.kernels.trigger_norms import trigger_sq_norms

KERNELS = ("trigger_sq_norms", "admm_update", "fused_gss")
SIZES = {
    "paper_mnist": dict(n=100, c=16, d=159_010),
    "large_population": dict(n=65_536, c=1_967, d=64),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device can be written to the persistent
    # cache but never read back without the chip: keep the cache off.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name, one_chip, n, c, d):
    def s(*shape, dt=jnp.float32):
        return _shape(one_chip, shape, dt)

    if name == "trigger_sq_norms":
        return (lambda z, w: trigger_sq_norms(z, w, interpret=False),
                (s(n, d), s(d)))
    if name == "admm_update":
        # The round runs the pre-solve form on the C gathered rows.
        return (lambda th, la, w: admm_update(th, la, w, interpret=False,
                                              with_z=False),
                (s(c, d), s(c, d), s(d)))
    return (lambda i, v, sol, w, th, la, z: fused_gss(
        i, v, sol, w, th, la, z, interpret=False),
        (s(c, dt=jnp.int32), s(c, dt=jnp.bool_), s(c, d), s(d),
         s(n, d), s(n, d), s(n, d)))


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, size):
    fn, args = _kernel_case(kernel, one_chip, **SIZES[size])
    # The round donates its state; the fused commit then updates the
    # donated θ/λ/z_prev buffers in place.
    donate = (4, 5, 6) if kernel == "fused_gss" else ()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    if donate:
        assert "input_output_alias={" in text


@pytest.mark.parametrize("variant,expected", [
    ("fused", ("trigger_sq_norms", "fused_gss")),
    ("unfused", ("trigger_sq_norms", "admm_update")),
])
def test_round_compiles_with_its_kernels(one_chip, monkeypatch, chip_smoke,
                                         request, variant, expected):
    from repro.core import init_state, make_round_fn
    from repro.kernels import ops

    # On this CPU backend the kernels would resolve to interpret mode.
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    # The kernels' jitted wrappers keep the trace made here; drop it, so
    # that a later CPU test at the same shapes traces interpret mode.
    request.addfinalizer(jax.clear_caches)
    cs = chip_smoke
    problem = cs.build_problem(seed=0, n_clients=16, n_train=1600,
                               n_test=100, hidden=8)
    cfg = cs.smoke_config(seed=0, kernels=True, n_clients=16,
                          fused_gss=variant == "fused")
    round_fn = make_round_fn(cfg, problem.loss_fn, problem.data,
                             spec=problem.spec, donate=True)
    state = jax.eval_shape(
        lambda p: init_state(cfg, p, spec=problem.spec), problem.params0)
    state = jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), state)
    calls = cs.kernel_calls(round_fn.lower(state).compile().as_text())
    for kernel in KERNELS:
        assert (calls[kernel] >= 1) == (kernel in expected), calls
