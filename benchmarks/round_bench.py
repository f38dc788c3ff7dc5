"""Round-engine benchmarks: the client-sharded simulation at scale.

Demonstrates the scaling claims of the device-mesh round engine:

* one FedBack round at **N ≥ 1000 clients** as a single XLA program
  (flat (N, D) client-state layout; sharded over every available local
  device via the ``clients`` mesh when more than one is present),
* **participation-proportional compute**: at L̄=0.25, slack=1.5 the
  capacity-bounded compacted round runs ⌈slack·L̄·N⌉ solver rows per
  round (≤ 0.5× the dense path's N) — state *and* data are gathered
  through the capacity slots, so the solver-side HBM model scales with
  C, not N — with training curves statistically matching the dense
  engine on the synthetic least-squares workload.  The deferral queue
  makes the compaction lossless (carried overflow, realized adaptive
  slack reported per section),
* a **multi-seed × controller-gain sweep compiled as ONE program**
  (scan-of-vmap, see ``repro.launch.sweep``).

Emits CSV rows (name, value, derived context) *and* a machine-readable
``BENCH_round.json`` (wall-clock per round, solver rows per round,
modeled server/solver HBM bytes from ``repro.launch.roofline``) — the
artifact the perf trajectory tracks.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn, pool_data, run_rounds
from repro.core.compact import capacity_for
from repro.data import make_least_squares
from repro.kernels.fused_gss import fused_gss_hbm_bytes
from repro.launch.roofline import fedback_async_overlap, \
    fedback_ragged_round_hbm_bytes, fedback_round_hbm_bytes, \
    host_stream_bytes
from repro.launch.sweep import init_sweep, make_sweep_fn, SweepGrid

BENCH_DIR = os.environ.get("BENCH_DIR", ".")


def _env_fingerprint() -> str:
    """Environment the wall-clock numbers were measured on — the
    bench-regression gate only compares timings on a matching
    fingerprint (same guard as the golden traces); rows/bytes/parity
    are compared unconditionally."""
    import platform
    return (f"jax={jax.__version__};backend={jax.default_backend()};"
            f"machine={platform.machine()}")


def _cfg(n_clients: int, n_points: int, **kw) -> FLConfig:
    base = dict(algorithm="fedback", n_clients=n_clients,
                participation=0.2, rho=1.0, lr=0.1, momentum=0.0,
                epochs=1, batch_size=n_points,
                controller=ControllerConfig(K=0.5, alpha=0.9))
    base.update(kw)
    return FLConfig(**base)


def _data_bytes_per_client(data) -> int:
    """fp32 bytes of one client's (x, y) shard — the data the solver
    streams per capacity slot."""
    per = 0
    for leaf in jax.tree.leaves(data):
        per += int(np.prod(leaf.shape[1:])) * 4
    return per


def _timed_rounds(round_fn, state, rounds: int, *, repeats: int = 1):
    """(compile_s, per_round_us, final_state, stacked_metrics).

    Round 0 doubles as the compile warm-up for timing purposes but its
    metrics are kept — it carries the full-participation burst (and,
    compacted, the dominant deferral term), so dropping it would skew
    the reported totals.  ``repeats`` re-times additional passes
    (continuing from the evolved state — same compiled program) and
    reports the **minimum** per-round time: small rounds are a couple
    of ms on CPU, where a single pass is scheduler-noise-dominated and
    would flake the ±15% bench-regression gate; the min over passes is
    the standard noise-robust wall-clock estimator.  Metrics come from
    the first pass only, so the reported trajectories stay those of
    rounds 0..rounds."""
    t0 = time.perf_counter()
    state, m0 = jax.block_until_ready(round_fn(state))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    state, hist = run_rounds(round_fn, state, rounds)
    hist = jax.device_get(jax.block_until_ready(hist))
    per_round_us = (time.perf_counter() - t0) / rounds * 1e6
    for _ in range(repeats - 1):
        t0 = time.perf_counter()
        state, extra = run_rounds(round_fn, state, rounds)
        jax.block_until_ready(extra)
        per_round_us = min(per_round_us,
                           (time.perf_counter() - t0) / rounds * 1e6)
    m0 = jax.device_get(m0)
    hist = jax.tree.map(
        lambda first, rest: np.concatenate(
            [np.asarray(first)[None], np.asarray(rest)]), m0, hist)
    return compile_s, per_round_us, state, hist


def run(print_fn=print, *, n_clients: int = 1024, n_points: int = 16,
        dim: int = 64, rounds: int = 5, compact_clients: int = 256,
        compact_rounds: int = 40, sweep_clients: int = 256,
        sweep_seeds: int = 4, sweep_gains: int = 2, sweep_rounds: int = 40):
    report: dict = {}
    data, params0, loss_fn = make_least_squares(n_clients, n_points, dim)
    spec = make_flat_spec(params0)
    cfg = _cfg(n_clients, n_points)

    # --- N >= 1000 client round (sharded over all local devices) -------
    n_dev = len(jax.devices())
    mesh = None
    if n_dev > 1:
        from repro.sharding.clients import make_client_mesh
        usable = max(d for d in range(1, n_dev + 1) if n_clients % d == 0)
        mesh = make_client_mesh(usable)
    state = init_state(cfg, params0, mesh=mesh, spec=spec)
    round_fn = make_round_fn(cfg, loss_fn, data, mesh=mesh, spec=spec)
    compile_s, per_round_us, state, hist = _timed_rounds(
        round_fn, state, rounds, repeats=3)
    devs = mesh.devices.size if mesh is not None else 1
    print_fn(f"fedback_round_n{n_clients},{per_round_us:.1f},"
             f"devices={devs} compile_s={compile_s:.2f} "
             f"events_r{rounds}={int(hist.num_events[-1])}")
    hbm = fedback_round_hbm_bytes(
        n_clients, n_clients, spec.dim,
        data_bytes_per_client=_data_bytes_per_client(data))
    report["dense_flat_n1024"] = {
        "n_clients": n_clients, "dim": spec.dim, "devices": devs,
        "per_round_us": per_round_us, "compile_s": compile_s,
        "solves_per_round": n_clients,
        "solver_rows_per_round": n_clients,
        "modeled_hbm_bytes_per_round": hbm["total_bytes"],
        "modeled_solver_hbm_bytes_per_round": hbm["solver_bytes"],
        "modeled_server_hbm_bytes_per_round": hbm["server_bytes"],
    }

    # --- participation-proportional compute: dense vs compacted --------
    rate, slack = 0.25, 1.5
    cdata, cparams0, closs = make_least_squares(compact_clients, n_points,
                                                dim)
    cspec = make_flat_spec(cparams0)
    curves = {}
    for name, compact in (("dense", False), ("compact", True)):
        ccfg = _cfg(compact_clients, n_points, participation=rate,
                    compact=compact, capacity_slack=slack)
        cstate = init_state(ccfg, cparams0, spec=cspec)
        crf = make_round_fn(ccfg, closs, cdata, spec=cspec)
        c_s, us, cstate, chist = _timed_rounds(crf, cstate,
                                               compact_rounds,
                                               repeats=3)
        solves = (capacity_for(compact_clients, rate, slack) if compact
                  else compact_clients)
        curves[name] = np.asarray(chist.train_loss, np.float64)
        chbm = fedback_round_hbm_bytes(
            compact_clients, int(solves), cspec.dim,
            data_bytes_per_client=_data_bytes_per_client(cdata))
        report[name] = {
            "n_clients": compact_clients, "dim": cspec.dim,
            "participation": rate, "capacity_slack": slack,
            "rounds": compact_rounds + 1,  # incl. the warm-up round 0
            "per_round_us": us, "compile_s": c_s,
            "solves_per_round": int(solves),
            "solver_rows_per_round": int(solves),
            # num_deferred is the queue *length* after each round, so the
            # sum counts client-rounds spent waiting (a client carried k
            # rounds contributes k), not deferral events.
            "deferred_client_rounds": int(np.sum(chist.num_deferred)),
            "queue_depth_final": int(np.asarray(chist.num_deferred)[-1]),
            "realized_slack_mean": float(
                np.mean(np.asarray(chist.realized_slack))),
            "realized_capacity_mean": float(
                np.mean(np.asarray(chist.realized_capacity))),
            "modeled_hbm_bytes_per_round": chbm["total_bytes"],
            "modeled_solver_hbm_bytes_per_round": chbm["solver_bytes"],
            "modeled_server_hbm_bytes_per_round": chbm["server_bytes"],
            "train_loss_curve": curves[name].tolist(),
            "final_train_loss": float(curves[name][-1]),
        }
        print_fn(f"fedback_{name}_n{compact_clients},{us:.1f},"
                 f"solves_per_round={int(solves)} "
                 f"realized_slack={report[name]['realized_slack_mean']:.2f} "
                 f"final_loss={curves[name][-1]:.5f}")

    tail = max(compact_rounds // 4, 1)
    d_tail = float(np.mean(curves["dense"][-tail:]))
    c_tail = float(np.mean(curves["compact"][-tail:]))
    ratio = report["compact"]["solves_per_round"] / \
        report["dense"]["solves_per_round"]
    rel = abs(c_tail - d_tail) / max(abs(d_tail), 1e-12)
    report["comparison"] = {
        "solver_rows_ratio": ratio,
        "solver_hbm_bytes_ratio": (
            report["compact"]["modeled_solver_hbm_bytes_per_round"]
            / report["dense"]["modeled_solver_hbm_bytes_per_round"]),
        "tail_loss_dense": d_tail,
        "tail_loss_compact": c_tail,
        "tail_loss_rel_err": rel,
        "curves_match": bool(rel < 0.1),
        "speedup_per_round": (report["dense"]["per_round_us"]
                              / max(report["compact"]["per_round_us"], 1e-9)),
    }
    print_fn(f"fedback_compact_vs_dense,{ratio:.3f},"
             f"tail_loss_rel_err={rel:.4f} "
             f"speedup={report['comparison']['speedup_per_round']:.2f}x")

    # --- fused gather→ADMM→scatter commit at N >= 1000 -----------------
    # The compacted round at benchmark scale with the fused commit
    # (kernels/fused_gss.py): λ⁺/z re-derived and scattered in ONE pass
    # over the (N, D) state instead of the reference three-scatter
    # commit.  Timed against the dense N=1024 round above (same N, same
    # D — the perf claim of this path), with the reference compacted
    # engine re-run at the same config to pin bit-parity (events AND ω)
    # as a benchmark flag the nightly compare job gates on.
    fcfg = _cfg(n_clients, n_points, participation=rate, compact=True,
                capacity_slack=slack, fused_gss=True)
    fstate = init_state(fcfg, params0, mesh=mesh, spec=spec)
    frf = make_round_fn(fcfg, loss_fn, data, mesh=mesh, spec=spec)
    f_s, f_us, fstate, fhist = _timed_rounds(frf, fstate, rounds,
                                             repeats=3)
    f_solves = capacity_for(n_clients, rate, slack)
    fhbm = fedback_round_hbm_bytes(
        n_clients, int(f_solves), spec.dim,
        data_bytes_per_client=_data_bytes_per_client(data), fused=True)
    # The kernel-level roofline the round-level solver-state model must
    # stay within 15% of — drift between the two means the round model
    # stopped tracking what the kernel actually streams.
    kernel_roofline = fused_gss_hbm_bytes(int(f_solves), spec.dim,
                                          with_z=True, presolve=True)
    roof_ratio = fhbm["solver_state_bytes"] / kernel_roofline
    # Bit-parity vs the reference three-pass commit, fresh states.
    refcfg = _cfg(n_clients, n_points, participation=rate, compact=True,
                  capacity_slack=slack, fused_gss=False)
    pf_state = init_state(fcfg, params0, mesh=mesh, spec=spec)
    pr_state = init_state(refcfg, params0, mesh=mesh, spec=spec)
    pr_rf = make_round_fn(refcfg, loss_fn, data, mesh=mesh, spec=spec)
    pf_state, pf_hist = run_rounds(frf, pf_state, 10)
    pr_state, pr_hist = run_rounds(pr_rf, pr_state, 10)
    fused_parity = bool(
        np.array_equal(np.asarray(pf_hist.events),
                       np.asarray(pr_hist.events))
        and np.asarray(pf_state.omega, np.float32).tobytes()
        == np.asarray(pr_state.omega, np.float32).tobytes())
    speedup = report["dense_flat_n1024"]["per_round_us"] / max(f_us, 1e-9)
    report["compact_fused"] = {
        "n_clients": n_clients, "dim": spec.dim, "devices": devs,
        "participation": rate, "capacity_slack": slack,
        "rounds": rounds + 1,
        "per_round_us": f_us, "compile_s": f_s,
        "solves_per_round": int(f_solves),
        "solver_rows_per_round": int(f_solves),
        "speedup_vs_dense": speedup,
        "speedup_ok": bool(speedup >= 1.3),
        "fused_parity_bitexact": fused_parity,
        "modeled_hbm_bytes_per_round": fhbm["total_bytes"],
        "modeled_solver_hbm_bytes_per_round": fhbm["solver_bytes"],
        "modeled_server_hbm_bytes_per_round": fhbm["server_bytes"],
        "modeled_solver_state_hbm_bytes_per_round":
            fhbm["solver_state_bytes"],
        "fused_gss_roofline_bytes": int(kernel_roofline),
        "solver_state_vs_roofline_ratio": roof_ratio,
        "roofline_within_15pct": bool(abs(roof_ratio - 1.0) <= 0.15),
    }
    print_fn(f"fedback_compact_fused_n{n_clients},{f_us:.1f},"
             f"speedup_vs_dense={speedup:.2f}x "
             f"parity={int(fused_parity)} "
             f"roofline_ratio={roof_ratio:.3f}")

    # --- stale-tolerant rounds: bounded-staleness commit pipeline ------
    # Same compacted workload with solves allowed to land up to S rounds
    # late; the consensus average runs every round over the freshest
    # available z-rows.  Solver rows per round are unchanged (the async
    # pipeline changes *when* results commit, never how many solves
    # run), so the bench-regression gate's no-solver-row-increase check
    # applies to these rows too.
    for staleness in (0, 2):
        acfg = _cfg(compact_clients, n_points, participation=rate,
                    compact=True, capacity_slack=slack,
                    max_staleness=staleness)
        astate = init_state(acfg, cparams0, spec=cspec)
        arf = make_round_fn(acfg, closs, cdata, spec=cspec)
        a_s, a_us, astate, ahist = _timed_rounds(
            arf, astate, compact_rounds, repeats=3)
        solves = capacity_for(compact_clients, rate, slack)
        overlap = fedback_async_overlap(
            compact_clients, int(solves), cspec.dim,
            max_staleness=staleness,
            data_bytes_per_client=_data_bytes_per_client(cdata))
        curve = np.asarray(ahist.train_loss, np.float64)
        name = f"compact_async_s{staleness}"
        report[name] = {
            "n_clients": compact_clients, "dim": cspec.dim,
            "participation": rate, "capacity_slack": slack,
            "max_staleness": staleness,
            "rounds": compact_rounds + 1,
            "per_round_us": a_us, "compile_s": a_s,
            "solves_per_round": int(solves),
            "solver_rows_per_round": int(solves),
            "landed_per_round_mean": float(
                np.mean(np.asarray(ahist.num_landed))),
            "inflight_depth_mean": float(
                np.mean(np.asarray(ahist.num_inflight))),
            "queue_depth_final": int(np.asarray(ahist.num_deferred)[-1]),
            "modeled_sync_s": overlap["modeled_sync_s"],
            "modeled_async_s": overlap["modeled_async_s"],
            "modeled_overlap_speedup": overlap["modeled_overlap_speedup"],
            "train_loss_curve": curve.tolist(),
            "final_train_loss": float(curve[-1]),
        }
        print_fn(f"fedback_{name}_n{compact_clients},{a_us:.1f},"
                 f"landed/round={report[name]['landed_per_round_mean']:.1f} "
                 f"inflight={report[name]['inflight_depth_mean']:.1f} "
                 f"modeled_overlap="
                 f"{overlap['modeled_overlap_speedup']:.2f}x "
                 f"final_loss={curve[-1]:.5f}")
    # staleness=0 must track the synchronous compacted engine exactly
    # (bit-identical events ⇒ identical loss curve) — surfaced so the
    # nightly compare job would catch an async-parity regression as a
    # benchmark diff even before the test suite runs.
    report["async_parity"] = {
        "s0_matches_sync_compact": bool(np.allclose(
            np.asarray(report["compact_async_s0"]["train_loss_curve"]),
            np.asarray(report["compact"]["train_loss_curve"]),
            rtol=1e-6, atol=1e-7)),
    }
    print_fn(f"fedback_async_parity,"
             f"{int(report['async_parity']['s0_matches_sync_compact'])},"
             f"staleness0_equals_sync")

    # --- ragged heterogeneous clients: Dirichlet-size CSR pool ---------
    # The same compacted workload with per-client shard sizes drawn from
    # a Dirichlet over clients (the heterogeneity the rectangular layout
    # trims away) pooled into one CSR buffer: the solver streams CSR
    # slices through the capacity slots, so solver rows per round are
    # unchanged and the HBM data term follows Σnᵢ, not nᵢ·N.
    r_points = 2 * n_points
    rdata, rparams0, rloss = make_least_squares(compact_clients, r_points,
                                                dim, seed=5)
    size_rng = np.random.default_rng(7)
    props = size_rng.dirichlet(np.full(compact_clients, 3.0))
    sizes = np.clip((props * compact_clients * r_points * 0.6).astype(int),
                    4, r_points)
    pooled, rrspec = pool_data(
        [np.asarray(rdata["x"][i])[:s] for i, s in enumerate(sizes)],
        [np.asarray(rdata["y"][i])[:s] for i, s in enumerate(sizes)])
    # Conservation, measured on the actual buffers (not the spec, which
    # is derived from the same inputs): every sliced row landed in the
    # pool — the regression this flag exists to catch is pool_data (or
    # a partition layer feeding it) dropping rows.
    conservation = bool(
        int(pooled["x"].shape[0]) - rrspec.padding == int(sizes.sum())
        and int(pooled["y"].shape[0]) - rrspec.padding == int(sizes.sum()))
    rcfg = _cfg(compact_clients, r_points, participation=rate,
                compact=True, capacity_slack=slack)
    rrspec_flat = make_flat_spec(rparams0)
    rstate = init_state(rcfg, rparams0, spec=rrspec_flat)
    rrf = make_round_fn(rcfg, rloss, pooled, spec=rrspec_flat,
                        ragged=rrspec)
    r_s, r_us, rstate, rhist = _timed_rounds(rrf, rstate, compact_rounds,
                                             repeats=3)
    r_solves = capacity_for(compact_clients, rate, slack)
    # one data row = one x feature vector + its scalar target, fp32
    row_bytes = 4 * (int(np.prod(rdata["x"].shape[2:])) + 1)
    rhbm = fedback_ragged_round_hbm_bytes(
        compact_clients, int(r_solves), rrspec_flat.dim,
        sizes=rrspec.sizes, row_bytes=row_bytes)
    # Uniform sizes must reproduce the rectangular compact engine bit
    # for bit (events AND ω) — surfaced as a benchmark flag so the
    # nightly compare job catches a ragged-parity regression even
    # before the test suite runs (same idea as async_parity).
    updata, upspec = pool_data(
        [np.asarray(cdata["x"][i]) for i in range(compact_clients)],
        [np.asarray(cdata["y"][i]) for i in range(compact_clients)])
    pcfg = _cfg(compact_clients, n_points, participation=rate,
                compact=True, capacity_slack=slack)
    pstate_a = init_state(pcfg, cparams0, spec=cspec)
    pstate_b = init_state(pcfg, cparams0, spec=cspec)
    prf_a = make_round_fn(pcfg, closs, cdata, spec=cspec)
    prf_b = make_round_fn(pcfg, closs, updata, spec=cspec, ragged=upspec)
    pstate_a, phist_a = run_rounds(prf_a, pstate_a, 10)
    pstate_b, phist_b = run_rounds(prf_b, pstate_b, 10)
    parity = bool(
        np.array_equal(np.asarray(phist_a.events),
                       np.asarray(phist_b.events))
        and np.array_equal(
            np.asarray(pstate_a.omega, np.float32).tobytes(),
            np.asarray(pstate_b.omega, np.float32).tobytes()))
    rcurve = np.asarray(rhist.train_loss, np.float64)
    report["ragged_dirichlet"] = {
        "n_clients": compact_clients, "dim": rrspec_flat.dim,
        "participation": rate, "capacity_slack": slack,
        "rounds": compact_rounds + 1,
        "per_round_us": r_us, "compile_s": r_s,
        "solves_per_round": int(r_solves),
        "solver_rows_per_round": int(r_solves),
        "data_rows_total": rrspec.total,
        "sizes_min": int(rrspec.min_size),
        "sizes_max": int(rrspec.max_size),
        "sizes_mean": float(np.mean(sizes)),
        "solve_buckets": len(rrspec.buckets),
        "conservation_ok": conservation,
        "uniform_parity_bitexact": parity,
        "modeled_hbm_bytes_per_round": rhbm["total_bytes"],
        "modeled_solver_hbm_bytes_per_round": rhbm["solver_bytes"],
        "modeled_server_hbm_bytes_per_round": rhbm["server_bytes"],
        "train_loss_curve": rcurve.tolist(),
        "final_train_loss": float(rcurve[-1]),
    }
    print_fn(f"fedback_ragged_dirichlet_n{compact_clients},{r_us:.1f},"
             f"rows={rrspec.total} sizes=[{rrspec.min_size},"
             f"{rrspec.max_size}] buckets={len(rrspec.buckets)} "
             f"uniform_parity={int(parity)} "
             f"final_loss={rcurve[-1]:.5f}")

    # --- sweep: seeds x gains as ONE compiled program -------------------
    grid = SweepGrid(seeds=tuple(range(sweep_seeds)),
                     gains=tuple(1.0 * (i + 1) for i in range(sweep_gains)))
    small = make_least_squares(sweep_clients, n_points, dim)
    scfg = _cfg(sweep_clients, n_points)
    sspec = make_flat_spec(small[1])
    n_runs = len(grid.runs(scfg))
    states, overrides, _ = init_sweep(scfg, small[1], grid, spec=sspec)
    sweep_fn = make_sweep_fn(scfg, small[2], small[0], rounds=sweep_rounds,
                             spec=sspec)
    t0 = time.perf_counter()
    final, shist = jax.block_until_ready(sweep_fn(states, overrides))
    first_s = time.perf_counter() - t0
    # min over repeats: the steady_us row feeds the 15%-tolerance
    # bench-regression gate, so a single noise-dominated pass won't do.
    steady_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        final, shist = jax.block_until_ready(sweep_fn(states, overrides))
        steady_s = min(steady_s, time.perf_counter() - t0)
    srate = float(jnp.mean(shist.events.astype(jnp.float32)))
    print_fn(f"fedback_sweep_{n_runs}runs_x{sweep_rounds}rounds,"
             f"{steady_s * 1e6:.1f},one_program=True "
             f"compile+run_s={first_s:.2f} realized_rate={srate:.3f}")
    report["sweep"] = {
        "runs": n_runs, "rounds": sweep_rounds, "one_program": True,
        "steady_us": steady_s * 1e6, "compile_plus_run_s": first_s,
        "realized_rate": srate,
    }

    # --- host-offloaded client state: double-buffered row streaming ----
    # state_backend="host" (core/hoststate.py): the (N, D) client
    # matrices live in host RAM; the device holds ω, the controller
    # vectors and a (C, D) working set streamed through the CompactPlan
    # slots.  Two scales at D=64: N=65536 timed, and the million-client
    # smoke — the demo that one host runs N=1e6 clients with
    # device-resident client-state bytes O(C·D), wall-clock tracking C.
    # Measured transfer counters are gated against the planned byte
    # model (round_fn.planned_bytes ≡ roofline.host_stream_bytes ≡ the
    # host-transfer-budget tracecheck rule).
    h_slack = 1.5
    phase_keys = ("plan_s", "h2d_s", "solve_s", "d2h_s", "scatter_s",
                  "agg_s")
    for sec, h_n, h_pts, h_rate, h_rounds, h_repeats in (
            ("host_stream_n65536", 65536, 4, 0.02, 3, 2),
            ("host_stream_n1m", 1_000_000, 2, 0.001, 2, 1)):
        hdata, hparams0, hloss = make_least_squares(h_n, h_pts, dim)
        hspec = make_flat_spec(hparams0)
        hcfg = _cfg(h_n, h_pts, participation=h_rate, compact=True,
                    capacity_slack=h_slack, state_backend="host")
        hstate = init_state(hcfg, hparams0, spec=hspec)
        hrf = make_round_fn(hcfg, hloss, hdata, spec=hspec)
        cap = int(capacity_for(h_n, h_rate, h_slack))
        planned = hrf.planned_bytes
        model = host_stream_bytes(
            h_n, cap, hspec.dim,
            data_bytes_per_client=_data_bytes_per_client(hdata))
        # Round 0 compiles all three programs and seeds the lazy
        # distance cache (one extra full-width H2D, priced below).
        t0 = time.perf_counter()
        hstate, hm0 = hrf(hstate)
        jax.block_until_ready((hstate.omega, hm0))
        h_compile_s = time.perf_counter() - t0
        snap = dict(hrf.stats)
        t0 = time.perf_counter()
        hstate, hhist = run_rounds(hrf, hstate, h_rounds)
        jax.block_until_ready((hstate.omega, hhist))
        wall_first_us = (time.perf_counter() - t0) / h_rounds * 1e6
        h_us = wall_first_us
        phase_us = {k: (hrf.stats[k] - snap[k]) / h_rounds * 1e6
                    for k in phase_keys}
        for _ in range(h_repeats - 1):
            t0 = time.perf_counter()
            hstate, extra = run_rounds(hrf, hstate, h_rounds)
            jax.block_until_ready((hstate.omega, extra))
            h_us = min(h_us, (time.perf_counter() - t0) / h_rounds * 1e6)
        # Measured counters vs plan.  Row streams must match the plan
        # exactly per round; the full-width leg is rounds × server pass
        # + the one-off distance seed (z_prev once, N·D·4).
        done = hrf.stats["rounds"]
        row_h2d_pr = hrf.stats["h2d_row_bytes"] / done
        row_d2h_pr = hrf.stats["d2h_row_bytes"] / done
        seed_bytes = h_n * hspec.dim * 4
        bytes_match = bool(
            row_h2d_pr == planned["row_stream_h2d"]
            and row_d2h_pr == planned["row_stream_d2h"]
            and hrf.stats["h2d_full_bytes"]
            == done * planned["server_pass_h2d"] + seed_bytes
            and hrf.stats["d2h_full_bytes"]
            == done * planned["server_pass_d2h"]
            and planned["row_stream_h2d"] == model["row_stream_h2d_bytes"]
            and planned["row_stream_d2h"] == model["row_stream_d2h_bytes"])
        # Phase timers tile the measured wall, so any *positive* gap of
        # Σphases over the wall is copy time hidden under compute; on
        # CPU transfers are memcpys on the compute thread, so the
        # honest measured fraction is ~0 (the modeled fraction is the
        # PCIe/HBM-roofline value a device part can hide).
        stream_us = phase_us["h2d_s"] + phase_us["d2h_s"]
        overlap_measured = max(
            0.0, (sum(phase_us.values()) - wall_first_us)
            / max(stream_us, 1e-9))
        report[sec] = {
            "n_clients": h_n, "dim": hspec.dim, "participation": h_rate,
            "capacity_slack": h_slack, "rounds": h_rounds + 1,
            "stream_tiles": hrf.static_info["tiles"],
            "per_round_us": h_us, "compile_s": h_compile_s,
            "solves_per_round": cap, "solver_rows_per_round": cap,
            "streamed_h2d_bytes_per_round": int(row_h2d_pr),
            "streamed_d2h_bytes_per_round": int(row_d2h_pr),
            "planned_h2d_bytes_per_round": planned["row_stream_h2d"],
            "planned_d2h_bytes_per_round": planned["row_stream_d2h"],
            "row_stream_budget_bytes": planned["row_stream_budget"],
            "server_pass_h2d_bytes_per_round": planned["server_pass_h2d"],
            "bytes_match_plan": bytes_match,
            "within_budget": bool(
                planned["row_stream_h2d"] + planned["row_stream_d2h"]
                <= planned["row_stream_budget"]),
            "device_state_bytes": int(hstate.device_state_bytes()),
            "host_state_bytes": int(hstate.host_state_bytes()),
            "device_state_sub_full_matrix": bool(
                hstate.device_state_bytes() < h_n * hspec.dim * 4),
            "plan_us": phase_us["plan_s"], "h2d_us": phase_us["h2d_s"],
            "solve_us": phase_us["solve_s"], "d2h_us": phase_us["d2h_s"],
            "scatter_us": phase_us["scatter_s"],
            "agg_us": phase_us["agg_s"],
            "overlap_fraction_measured": overlap_measured,
            "modeled_overlap_fraction": model["modeled_overlap_fraction"],
            "modeled_stream_s": model["stream_s"],
            "modeled_solve_s": model["solve_s"],
            "events_final": int(np.asarray(hhist.num_events)[-1]),
        }
        print_fn(
            f"fedback_{sec},{h_us:.1f},"
            f"C={cap} h2d/round={int(row_h2d_pr)}B "
            f"d2h/round={int(row_d2h_pr)}B "
            f"bytes_match_plan={int(bytes_match)} "
            f"device_state={int(hstate.device_state_bytes())}B "
            f"overlap={overlap_measured:.2f}"
            f"/{model['modeled_overlap_fraction']:.2f}(model)")
        del hdata, hstate, hrf  # free the (N, ...) buffers before 1M

    # Bit-parity vs the device backend at small N: same config modulo
    # state_backend, 10 rounds, events AND the fp32 client matrices
    # must agree byte for byte (same flag pattern as fused/async/ragged
    # parity — the nightly compare job gates on it unconditionally).
    hp_n, hp_rate = compact_clients, 0.25
    hpcfg_d = _cfg(hp_n, n_points, participation=hp_rate, compact=True,
                   capacity_slack=h_slack, state_backend="device")
    hpcfg_h = _cfg(hp_n, n_points, participation=hp_rate, compact=True,
                   capacity_slack=h_slack, state_backend="host")
    hp_state_d = init_state(hpcfg_d, cparams0, spec=cspec)
    hp_state_h = init_state(hpcfg_h, cparams0, spec=cspec)
    hp_rf_d = make_round_fn(hpcfg_d, closs, cdata, spec=cspec)
    hp_rf_h = make_round_fn(hpcfg_h, closs, cdata, spec=cspec)
    hp_state_d, hp_hist_d = run_rounds(hp_rf_d, hp_state_d, 10)
    hp_state_h, hp_hist_h = run_rounds(hp_rf_h, hp_state_h, 10)
    host_parity = bool(
        np.array_equal(np.asarray(hp_hist_d.events),
                       np.asarray(hp_hist_h.events))
        and all(
            np.asarray(getattr(hp_state_d, f), np.float32).tobytes()
            == np.asarray(getattr(hp_state_h, f), np.float32).tobytes()
            for f in ("omega", "theta", "lam", "z_prev")))
    report["host_parity"] = {"host_parity_bitexact": host_parity}
    print_fn(f"fedback_host_parity,{int(host_parity)},"
             f"host_equals_device_bitexact_n{hp_n}")

    report["_env"] = _env_fingerprint()
    path = os.path.join(BENCH_DIR, "BENCH_round.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print_fn(f"bench_json,{path},sections={len(report)}")
    return report


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
