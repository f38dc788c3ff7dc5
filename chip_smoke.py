"""Chip smoke test: the FedBack round engine on a TPU at the paper's
MNIST width, through the entry points a user calls.

    python chip_smoke.py            # one chip: all phases below
    python chip_smoke.py --chips 4  # the client-sharded round on a
                                    # 4-chip mesh vs one chip, only

The deployment is the paper's §5 MNIST run as ``configs/paper_mnist``
defines it — N = 100 clients with two labels each (``label_shard``), a
784-200-10 MLP (D = 159,010 flat parameters, random weights from
``--seed``), L̄ = 0.1, ρ = 0.01, K = 2, α = 0.9, 2 local epochs of
batch 42 — on the compacted engine (C = 16 solver rows) with the flat
state layout and all three Pallas kernels on.  The data is a synthetic
MNIST of MNIST's size (60,000 training examples), generated from the
seed; nothing is read from disk.

Phases, one output line each:

1. device — ``jax.devices()``; anything but a TPU exits non-zero.
2. compile — compiles the round (and its unfused variant, the one
   that runs ``admm_update``), times it, prints ``memory_analysis()``
   and requires a ``tpu_custom_call`` for each of the three kernels.
3. parity — 5 rounds with the kernels against 5 rounds of the jnp
   reference from a fresh state: bit-identical events and committed
   clients, ω allclose (rtol 1e-5), finite train loss, events in the
   run and at least one committed client per round; then test accuracy
   of the kernel run.
4. host — 3 rounds with ``state_backend="host"``: the same events.
5. serve — ``serve()`` over a 30-tick bursty arrival trace: at least
   one commit, finite latencies, conserved admissions.

With ``--chips 4`` only the mesh phase runs: the same round with
capacity = N (so that per-shard planning serves exactly the clients a
single device serves) on ``make_client_mesh(4)`` against one device,
requiring identical events, θ spread over 4 devices, ω allclose after
the first round and within ``MESH_REL_L2`` in relative L2 norm after
every round.

The last line is one JSON object: ``{"ok": true, "device": {...}}``.
Any failed phase raises, so the script exits non-zero and prints no
such line.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

KERNELS = ("trigger_sq_norms", "admm_update", "fused_gss")
N_CLIENTS = 100
PARTICIPATION = 0.1
N_TRAIN = 60_000
N_TEST = 10_000
ROUNDS = 5
HOST_ROUNDS = 3
SERVE_TICKS = 30
# Bound on ||omega_mesh - omega_one|| / ||omega_one|| over the rounds of
# the mesh phase.  On a TPU v5e the default fp32 matmul precision alone
# moves omega by 5.5e-4 in this norm after 5 rounds on one device
# (default vs "highest"); the 4-chip mesh moved it by 6.9e-5.
MESH_REL_L2 = 5e-4


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **fields) -> None:
    items = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"phase={phase} {items}", flush=True)


def _import_repro():
    """Import the program from this checkout's ``src`` and nowhere else."""
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    import repro

    paths = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(SRC + os.sep) for p in paths):
        raise ImportError(f"repro resolved to {paths}, not {SRC}")
    return repro


@dataclasses.dataclass
class Problem:
    data: dict
    test: dict
    params0: dict
    spec: object
    loss_fn: object
    loss_and_acc_fn: object


def build_problem(*, seed: int, n_clients: int = N_CLIENTS,
                  n_train: int = N_TRAIN, n_test: int = N_TEST,
                  hidden: int = 200) -> Problem:
    """Synthetic MNIST split by two labels per client; the MLP at init."""
    import jax

    from repro.core import make_flat_spec
    from repro.data import federated_arrays, make_synthetic_mnist
    from repro.models.mlp import (init_mlp, make_loss_and_acc_fn,
                                  make_loss_fn, mlp_logits)

    ds = make_synthetic_mnist(n_train=n_train, n_test=n_test, seed=seed)
    data, test = federated_arrays(ds, n_clients=n_clients,
                                  scheme="label_shard", seed=seed)
    params0 = init_mlp(jax.random.PRNGKey(seed), hidden=hidden)
    return Problem(data=data, test=test, params0=params0,
                   spec=make_flat_spec(params0),
                   loss_fn=make_loss_fn(mlp_logits),
                   loss_and_acc_fn=make_loss_and_acc_fn(mlp_logits))


def smoke_config(*, seed: int, kernels: bool, n_clients: int = N_CLIENTS,
                 **kw):
    """The paper's MNIST FedBack config on the compacted flat engine."""
    from repro.configs import paper_mnist

    flags = dict(use_trigger_kernel=kernels, use_admm_kernel=kernels,
                 fused_gss=kernels)
    flags.update(kw)
    return paper_mnist.fl_config("fedback", PARTICIPATION,
                                 n_clients=n_clients, compact=True,
                                 seed=seed, **flags)


def kernel_calls(hlo_text: str) -> dict:
    """``tpu_custom_call`` sites per kernel in a compiled module, found
    by the kernel's jitted wrapper name in each call's op metadata."""
    counts = dict.fromkeys(KERNELS, 0)
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for name in KERNELS:
            if f"jit({name})/" in line:
                counts[name] += 1
    return counts


def phase_device() -> dict:
    import jax

    devices = jax.devices()
    kinds = sorted({d.device_kind for d in devices})
    report("device", devices=devices, platform=devices[0].platform,
           kinds=kinds, count=len(devices), jax=jax.__version__)
    check(devices[0].platform == "tpu",
          f"no TPU: JAX platform is {devices[0].platform!r}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def _compile(problem: Problem, cfg):
    from repro.core import init_state, make_round_fn

    round_fn = make_round_fn(cfg, problem.loss_fn, problem.data,
                             spec=problem.spec)
    state = init_state(cfg, problem.params0, spec=problem.spec)
    t0 = time.perf_counter()
    compiled = round_fn.lower(state).compile()
    return round_fn, state, compiled, time.perf_counter() - t0


def phase_compile(problem: Problem, *, seed: int, n_clients: int = N_CLIENTS,
                  require_kernels: bool = True) -> dict:
    """Compile the kernel round and its unfused variant; on a TPU each
    of the three kernels must be a ``tpu_custom_call`` in one of them:
    trigger_sq_norms + fused_gss in the round, trigger_sq_norms +
    admm_update in the variant (the fused commit replaces the
    admm_update pass by design)."""
    from repro.core.compact import capacity_bounds

    variants = {
        "fused": (smoke_config(seed=seed, kernels=True,
                               n_clients=n_clients),
                  ("trigger_sq_norms", "fused_gss")),
        "unfused": (smoke_config(seed=seed, kernels=True,
                                 n_clients=n_clients, fused_gss=False),
                    ("trigger_sq_norms", "admm_update")),
    }
    out = {}
    for name, (cfg, expected) in variants.items():
        round_fn, state, compiled, seconds = _compile(problem, cfg)
        calls = kernel_calls(compiled.as_text())
        _, capacity = capacity_bounds(cfg.n_clients, cfg.participation,
                                      cfg.capacity_slack, cfg.capacity)
        report("compile", variant=name, compile_s=seconds,
               dim=problem.spec.dim, capacity=capacity,
               tpu_custom_calls=calls,
               memory=compiled.memory_analysis())
        if require_kernels:
            for kernel in expected:
                check(calls[kernel] >= 1,
                      f"{name} round: no tpu_custom_call for {kernel}")
        out[name] = (cfg, round_fn, state)
    return out


def _run(round_fn, state, rounds: int):
    import jax
    import numpy as np

    from repro.core import run_rounds

    state, metrics = run_rounds(round_fn, state, rounds)
    metrics = jax.device_get(metrics)
    return (state, np.asarray(metrics.events),
            np.asarray(metrics.train_loss), np.asarray(metrics.committed))


def phase_parity(problem: Problem, compiled: dict, *, seed: int,
                 n_clients: int = N_CLIENTS, rounds: int = ROUNDS) -> dict:
    """Kernel rounds (both compiled variants) vs the jnp reference."""
    import numpy as np

    from repro.core import init_state, make_eval_fn, make_round_fn

    ref_cfg = smoke_config(seed=seed, kernels=False, n_clients=n_clients)
    ref_fn = make_round_fn(ref_cfg, problem.loss_fn, problem.data,
                           spec=problem.spec)
    ref_state, ref_events, _, ref_committed = _run(
        ref_fn, init_state(ref_cfg, problem.params0, spec=problem.spec),
        rounds)
    ref_omega = np.asarray(ref_state.omega)
    del ref_state
    eval_fn = make_eval_fn(problem.loss_and_acc_fn, spec=problem.spec)
    for name, (_, round_fn, state) in compiled.items():
        state, events, loss, committed = _run(round_fn, state, rounds)
        omega = np.asarray(state.omega)
        check(np.array_equal(events, ref_events),
              f"{name}: events differ from the jnp reference in rounds "
              f"{np.flatnonzero((events != ref_events).any(axis=1))}")
        check(np.allclose(omega, ref_omega, rtol=1e-5, atol=1e-6),
              f"{name}: omega differs from the jnp reference by "
              f"{np.max(np.abs(omega - ref_omega))}")
        check(bool(np.isfinite(loss).all()),
              f"{name}: non-finite train loss {loss}")
        check(np.array_equal(committed, ref_committed),
              f"{name}: committed clients differ from the jnp reference")
        check(bool(events.any()), f"{name}: no event in {rounds} rounds")
        # The controller may silence the trigger for a round after the
        # all-fire start; the deferred queue still commits clients.
        check(bool((committed.sum(axis=1) >= 1).all()),
              f"{name}: a round commits no client {committed.sum(axis=1)}")
        _, acc = eval_fn(state, problem.test["x"], problem.test["y"])
        report("parity", variant=name, rounds=rounds,
               events_per_round=events.sum(axis=1).tolist(),
               committed_per_round=committed.sum(axis=1).tolist(),
               train_loss=loss.tolist(),
               max_abs_omega_diff=float(np.max(np.abs(omega - ref_omega))),
               test_accuracy=float(acc))
        del state
    return {"events": ref_events}


def phase_host(problem: Problem, ref_events, *, seed: int,
               n_clients: int = N_CLIENTS,
               rounds: int = HOST_ROUNDS) -> None:
    """The host-offloaded backend reproduces the device events."""
    import numpy as np

    from repro.core import init_state, make_round_fn

    cfg = smoke_config(seed=seed, kernels=True, n_clients=n_clients,
                       state_backend="host")
    round_fn = make_round_fn(cfg, problem.loss_fn, problem.data,
                             spec=problem.spec)
    state = init_state(cfg, problem.params0, spec=problem.spec)
    _, events, loss, _ = _run(round_fn, state, rounds)
    check(np.array_equal(events, ref_events[:rounds]),
          "host backend events differ from the device backend")
    check(bool(np.isfinite(loss).all()), f"host: non-finite loss {loss}")
    report("host", rounds=rounds,
           events_per_round=events.sum(axis=1).tolist())


def phase_serve(problem: Problem, *, seed: int, n_clients: int = N_CLIENTS,
                ticks: int = SERVE_TICKS) -> None:
    """Serve a bursty arrival trace through the jitted admission step."""
    import numpy as np

    from repro.core import init_state, make_round_fn
    from repro.core.schedule import TraceConfig, make_trace, serve

    cfg = smoke_config(seed=seed, kernels=True, n_clients=n_clients)
    round_fn = make_round_fn(cfg, problem.loss_fn, problem.data,
                             spec=problem.spec, arrivals_arg=True)
    state = init_state(cfg, problem.params0, spec=problem.spec)
    trace = make_trace(TraceConfig(kind="bursty", n_clients=n_clients,
                                   ticks=ticks, seed=seed))
    _, rep = serve(round_fn, state, trace, warmup=True)
    check(rep.commits_total >= 1, "serve: no commit")
    check(bool(np.isfinite(rep.latency_us).all()
               and np.isfinite(rep.latency_ticks).all()),
          "serve: non-finite latency")
    check(rep.conservation_ok, "serve: admissions not conserved")
    summary = rep.summary()
    report("serve", **{k: summary[k] for k in (
        "ticks", "arrivals_total", "admitted_total", "commits_total",
        "pending_final", "p50_latency_ticks", "p99_latency_ticks",
        "p50_latency_us", "p99_latency_us", "wall_s")})


def phase_mesh(problem: Problem, *, seed: int, n_devices: int = 4,
               n_clients: int = N_CLIENTS, rounds: int = ROUNDS) -> None:
    """The client-sharded round on an n-device mesh vs one device."""
    import numpy as np

    from repro.core import init_state, make_round_fn
    from repro.sharding.clients import make_client_mesh

    cfg = smoke_config(seed=seed, kernels=True, n_clients=n_clients,
                       capacity=n_clients)
    mesh = make_client_mesh(n_devices)
    results, spread = {}, []
    for name, m in (("single", None), ("mesh", mesh)):
        round_fn = make_round_fn(cfg, problem.loss_fn, problem.data,
                                 spec=problem.spec, mesh=m)
        state = init_state(cfg, problem.params0, spec=problem.spec, mesh=m)
        events, omegas, losses = [], [], []
        t0 = time.perf_counter()
        for _ in range(rounds):
            if m is not None:
                spread.append(len(state.theta.sharding.device_set))
            state, metrics = round_fn(state)
            events.append(np.asarray(metrics.events))
            omegas.append(np.asarray(state.omega))
            losses.append(float(metrics.train_loss))
        results[name] = (np.stack(events), omegas, losses,
                         time.perf_counter() - t0)
        del state
    (ev1, om1, _, _), (ev4, om4, loss4, wall4) = (results["single"],
                                                  results["mesh"])
    rel = [float(np.linalg.norm(b - a) / np.linalg.norm(a))
           for a, b in zip(om1, om4, strict=True)]
    report("mesh", devices=n_devices, rounds=rounds, theta_devices=spread,
           events_per_round=ev4.sum(axis=1).tolist(), train_loss=loss4,
           rel_l2_omega_diff_per_round=rel,
           max_abs_omega_diff=float(np.max(np.abs(om4[-1] - om1[-1]))),
           wall_s_incl_compile=wall4)
    check(all(s == n_devices for s in spread),
          f"theta is not spread over {n_devices} devices: {spread}")
    check(np.array_equal(ev1, ev4), "mesh events differ from one device")
    # After one round the two programs differ only in the order of
    # their fp32 sums.  Later rounds train from those ulp-level
    # differences, and the local SGD solve amplifies them, so the
    # trajectories are compared in relative L2 norm against a bound
    # below the spread the TPU's own matmul precision makes on one
    # device (see MESH_REL_L2).
    check(np.allclose(om4[0], om1[0], rtol=1e-5, atol=1e-6),
          f"mesh omega after round 1 differs by "
          f"{np.max(np.abs(om4[0] - om1[0]))}")
    check(max(rel) <= MESH_REL_L2,
          f"mesh omega drifts from one device: relative L2 {rel}")
    check(bool(np.isfinite(loss4).all()), f"mesh: non-finite loss {loss4}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the FedBack round engine on a TPU and check it.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repro()
    from repro.utils.compile_cache import enable_compile_cache

    device = phase_device()
    if args.chips > device["count"]:
        raise SmokeFailure(f"--chips {args.chips} but JAX sees "
                           f"{device['count']} device(s)")
    report("cache", dir=enable_compile_cache())
    t0 = time.perf_counter()
    problem = build_problem(seed=args.seed)
    report("data", x=tuple(problem.data["x"].shape),
           x_mb=problem.data["x"].nbytes / 1e6, dim=problem.spec.dim,
           build_s=time.perf_counter() - t0)
    if args.chips == 4:
        phase_mesh(problem, seed=args.seed)
    else:
        compiled = phase_compile(problem, seed=args.seed)
        ref = phase_parity(problem, compiled, seed=args.seed)
        del compiled
        phase_host(problem, ref["events"], seed=args.seed)
        phase_serve(problem, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
