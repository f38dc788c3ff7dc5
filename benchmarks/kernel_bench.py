"""Kernel microbenchmarks: Pallas kernels vs. jnp reference.

The kernels run in the mode ``interpret=None`` resolves to: compiled on
a TPU, interpret mode elsewhere, where the timing measures *correct
execution* of the exact TPU program (not TPU speed) and the row names
say ``interpret``.  The derived column reports the analytic roofline
time for each kernel's traffic, priced at the peaks of the chip the
bench runs on (``repro.launch.roofline.peaks``) or, off the chip, of
the modeled v5e.

Emits CSV rows and a machine-readable ``BENCH_kernels.json`` (µs per
call, modeled HBM bytes, TPU roofline µs per kernel).
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.launch.roofline import MODEL_KIND, peaks

BENCH_DIR = os.environ.get("BENCH_DIR", ".")


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # µs


def run(print_fn=print):
    dev = jax.devices()[0]
    chip = peaks(dev.device_kind if dev.platform == "tpu" else MODEL_KIND)
    hbm_bw = chip["hbm_bw"]
    mode = "interpret" if ops._default_interpret() else "compiled"
    rng = np.random.default_rng(0)
    report: dict = {}

    def record(name, us, *, hbm_bytes=None, tpu_roofline_us=None,
               flops=None, note=None):
        entry = {"us_per_call": us}
        if hbm_bytes is not None:
            entry["modeled_hbm_bytes"] = hbm_bytes
        if tpu_roofline_us is not None:
            entry["tpu_roofline_us"] = tpu_roofline_us
        if flops is not None:
            entry["flops"] = flops
        if note:
            entry["note"] = note
        report[name] = entry

    print_fn("name,us_per_call,derived")

    # trigger norms: 100 clients × 159k params (paper MNIST scale)
    n, d = 100, 159_010
    z = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    us_ref = _time(jax.jit(ops.trigger_sq_norms_ref), z, w)
    bytes_moved = (n * d + d) * 4
    tpu_us = bytes_moved / hbm_bw * 1e6
    print_fn(f"trigger_norms_ref_jnp,{us_ref:.1f},"
             f"tpu_roofline_us={tpu_us:.1f}")
    record("trigger_norms_ref_jnp", us_ref, hbm_bytes=bytes_moved,
           tpu_roofline_us=tpu_us)

    # admm fused update (3-output form; the round uses with_z=False)
    th = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    la = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    us_ref = _time(jax.jit(lambda a, b, c: ops.admm_update_ref(a, b, c)),
                   th, la, w)
    bytes_moved = n * d * 4 * 5  # 2 reads + 3 writes (ω cached)
    tpu_us = bytes_moved / hbm_bw * 1e6
    print_fn(f"admm_update_ref_jnp,{us_ref:.1f},"
             f"tpu_roofline_us={tpu_us:.1f}")
    record("admm_update_ref_jnp", us_ref, hbm_bytes=bytes_moved,
           tpu_roofline_us=tpu_us)
    # pre-solve form: λ⁺ + center only, 4 streams instead of 5.  No
    # measured time — modeled roofline only, so us_per_call stays null.
    bytes_pre = n * d * 4 * 4
    report["admm_update_presolve_modeled"] = {
        "us_per_call": None, "modeled_hbm_bytes": bytes_pre,
        "tpu_roofline_us": bytes_pre / hbm_bw * 1e6,
        "note": "with_z=False round form (2 reads + 2 writes)",
    }

    # fused gather→ADMM→scatter commit (compact-round capacity slots):
    # C=384 planned rows of an N=1024 state, paper-scale D.  The jnp
    # reference is the measured CPU number; the modeled row is the
    # kernel's one-pass traffic (7 streams + ω, fused_gss_hbm_bytes).
    from repro.kernels.fused_gss import fused_gss_hbm_bytes
    gn, gc, gd = 1024, 384, 4096
    gth = jnp.asarray(rng.normal(size=(gn, gd)), jnp.float32)
    gla = jnp.asarray(rng.normal(size=(gn, gd)), jnp.float32)
    gz = jnp.asarray(rng.normal(size=(gn, gd)), jnp.float32)
    gw = jnp.asarray(rng.normal(size=(gd,)), jnp.float32)
    gsolved = jnp.asarray(rng.normal(size=(gc, gd)), jnp.float32)
    gidx = jnp.asarray(rng.permutation(gn)[:gc], jnp.int32)
    gvalid = jnp.asarray(rng.random(gc) < 0.9)
    us_ref = _time(jax.jit(lambda *a: ops.fused_gss_ref(*a, with_z=True)),
                   gidx, gvalid, gsolved, gw, gth, gla, gz)
    bytes_moved = fused_gss_hbm_bytes(gc, gd, with_z=True)
    tpu_us = bytes_moved / hbm_bw * 1e6
    print_fn(f"fused_gss_ref_jnp,{us_ref:.1f},"
             f"tpu_roofline_us={tpu_us:.1f}")
    record("fused_gss_ref_jnp", us_ref, hbm_bytes=bytes_moved,
           tpu_roofline_us=tpu_us)
    # reference three-pass commit traffic over the same rows: θ/λ
    # gathers (2 reads + 2 compact writes), z assembly (2 reads + 1
    # write), three scatter writes — ~10 streams vs the kernel's 7.
    bytes_3pass = 4 * (10 * gc * gd + gd)
    report["fused_gss_unfused_3pass_modeled"] = {
        "us_per_call": None, "modeled_hbm_bytes": bytes_3pass,
        "tpu_roofline_us": bytes_3pass / hbm_bw * 1e6,
        "note": "reference gather + z-assembly + 3-scatter commit "
                "traffic over the same planned rows",
    }

    # flash attention (single head-block workload)
    b, h, kvh, s, hd = 1, 8, 2, 1024, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(b, kvh, s, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(b, kvh, s, hd)), jnp.bfloat16)
    us_ref = _time(jax.jit(
        lambda q, k, v: ops.flash_attention_ref(q, k, v)), q, k, v)
    flops = 2 * 2 * b * h * s * s * hd  # qk + pv
    tpu_us = flops / chip["bf16_flops"] * 1e6
    print_fn(f"flash_attention_ref_jnp,{us_ref:.1f},"
             f"tpu_compute_roofline_us={tpu_us:.2f}")
    record("flash_attention_ref_jnp", us_ref, flops=flops,
           tpu_roofline_us=tpu_us)

    # ssd inter-chunk scan
    bb, c, hh, p, nn = 4, 64, 80, 64, 128
    states = jnp.asarray(rng.normal(size=(bb, c, hh, p, nn)), jnp.float32)
    decays = jnp.asarray(rng.uniform(0.5, 0.99, (bb, c, hh)), jnp.float32)
    us_ref = _time(jax.jit(lambda s_, d_: ops.ssd_scan_ref(s_, d_)[0]),
                   states, decays)
    bytes_moved = states.size * 4 * 2
    tpu_us = bytes_moved / hbm_bw * 1e6
    print_fn(f"ssd_scan_ref_jnp,{us_ref:.1f},"
             f"tpu_roofline_us={tpu_us:.1f}")
    record("ssd_scan_ref_jnp", us_ref, hbm_bytes=bytes_moved,
           tpu_roofline_us=tpu_us)

    # Pallas kernels at a small slice (off the chip: interpret mode, a
    # correctness-path timing and a CPU-only number)
    us_k = _time(lambda: ops.trigger_sq_norms(z[:8, :4096], w[:4096]))
    print_fn(f"trigger_norms_pallas_{mode}_small,{us_k:.1f},mode={mode}")
    record(f"trigger_norms_pallas_{mode}_small", us_k,
           note=f"{mode} mode")

    us_k = _time(lambda: ops.admm_update(th[:8, :4096], la[:8, :4096],
                                         w[:4096], with_z=False)[0])
    print_fn(f"admm_update_pallas_{mode}_small,{us_k:.1f},"
             f"mode={mode} with_z=False")
    record(f"admm_update_pallas_{mode}_small", us_k,
           note=f"{mode} mode, with_z=False (round form)")

    us_k = _time(lambda: ops.fused_gss(
        gidx[:8], gvalid[:8], gsolved[:8, :4096], gw[:4096],
        gth[:, :4096], gla[:, :4096], gz[:, :4096])[0])
    print_fn(f"fused_gss_pallas_{mode}_small,{us_k:.1f},"
             f"mode={mode} with_z=True")
    record(f"fused_gss_pallas_{mode}_small", us_k,
           note=f"{mode} mode, 8 slots of the (1024, 4096) state")

    import platform
    report["_env"] = (f"jax={jax.__version__};"
                      f"backend={jax.default_backend()};"
                      f"machine={platform.machine()}")
    path = os.path.join(BENCH_DIR, "BENCH_kernels.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print_fn(f"bench_json,{path},kernels={len(report)}")
    return report


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
