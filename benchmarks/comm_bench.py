"""Compressed-consensus benchmark → ``BENCH_comm.json``.

The communication story of the compressed wire (``core/compress.py``),
as a machine-readable artifact the nightly-bench gate tracks:

* **bytes on wire per round** — the modeled consensus collective term
  per ``consensus_compress`` mode (ring all-reduce at the wire dtype,
  u16 all-gather for bf16, the int8 shared-scale overhead accounted
  separately), from the same :func:`repro.core.compress.
  consensus_wire_bytes` model tracecheck's ``CollectiveBudget`` prices
  its budgets with.  The int8-vs-fp32 payload ratio here is the
  acceptance number (≤ 0.3×), and ``benchmarks/compare.py`` gates every
  byte figure as never-increase against the committed baseline;

* **rounds-to-target under compression × participation rate** — small
  fixed-seed FedBack runs on the synthetic least-squares workload, one
  per (participation, mode) grid point, measuring the round at which
  the global loss at ω first covers 95% of the fp32 anchor's
  first-to-final loss descent at the same participation rate (an
  absolute-final-loss target would sit just above the consensus floor
  and be reached immediately — the *descent* fraction is what
  discriminates).  Error feedback is doing its job exactly when the
  compressed legs reach the target within tolerance of the anchor —
  the convergence-rounds gate in ``compare.py``.

Emits CSV-ish progress lines and writes ``BENCH_comm.json`` to
``$BENCH_DIR`` (default "."), with the same ``_env`` fingerprint
convention as the other bench artifacts.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ControllerConfig, FLConfig, init_state, \
    make_flat_spec, make_round_fn
from repro.core.compress import MODES, WIRE_BYTES
from repro.data import make_least_squares
from repro.launch.roofline import consensus_collective_s

BENCH_DIR = os.environ.get("BENCH_DIR", ".")

#: Bench problem (fixed: the grid is seed-deterministic end to end).
N_CLIENTS = 64
N_POINTS = 8
DIM = 32
ROUNDS = 60
SEED = 0
BLOCK = 256
WORLD_SIZE = 2          # the modeled mesh of the wire-bytes section
PARTICIPATION_GRID = (0.1, 0.25, 0.5)
TARGET_DESCENT = 0.95   # fraction of the fp32 anchor's first-to-final
#                         loss descent the target sits at


def _env_fingerprint() -> str:
    import platform
    return (f"jax={jax.__version__};backend={jax.default_backend()};"
            f"machine={platform.machine()}")


def _grid_name(rate: float, mode: str) -> str:
    return f"conv_p{int(round(rate * 100))}_{mode}"


def wire_sections(report: dict, print_fn=print) -> None:
    for mode in MODES:
        wire = consensus_collective_s(DIM, mode=mode, block=BLOCK,
                                      world_size=WORLD_SIZE)
        report[f"wire_{mode}"] = {
            "dim": DIM, "block": BLOCK, "world_size": WORLD_SIZE,
            "wire_bytes_per_coord": WIRE_BYTES[mode], **wire,
        }
        print_fn(f"comm_wire_{mode},{wire['total_link_bytes']:.1f},"
                 f"payload={wire['payload_link_bytes']:.1f} "
                 f"uplink={wire['uplink_bytes_per_client']}")
    fp32 = report["wire_none"]["payload_link_bytes"]
    report["wire_ratio"] = {
        "int8_vs_fp32": report["wire_int8"]["payload_link_bytes"] / fp32,
        "bf16_vs_fp32": report["wire_bf16"]["payload_link_bytes"] / fp32,
        "int8_total_vs_fp32": (report["wire_int8"]["total_link_bytes"]
                               / fp32),
    }
    print_fn(f"comm_wire_ratio_int8,"
             f"{report['wire_ratio']['int8_vs_fp32']:.3f},"
             f"bf16={report['wire_ratio']['bf16_vs_fp32']:.3f}")


def _global_loss_fn(data, loss_fn, spec):
    """Jitted mean loss over EVERY client's shard at the server ω —
    the convergence measurement (participant-set independent, unlike
    the per-round train_loss metric)."""

    def global_loss(omega):
        params = spec.unflatten(omega)
        per = jax.vmap(lambda x, y: loss_fn(params, x, y))(
            data["x"], data["y"])
        return jnp.mean(per)

    return jax.jit(global_loss)


def _run_leg(rate: float, mode: str, data, params0, loss_fn, spec):
    """Loss-at-ω curve of one (participation, mode) grid point."""
    cfg = FLConfig(algorithm="fedback", n_clients=N_CLIENTS,
                   participation=rate, rho=1.0, lr=0.1, momentum=0.0,
                   epochs=1, batch_size=N_POINTS, seed=SEED,
                   consensus_compress=mode, compress_block=BLOCK,
                   controller=ControllerConfig(K=0.5, alpha=0.9))
    state = init_state(cfg, params0, spec=spec)
    round_fn = make_round_fn(cfg, loss_fn, data, spec=spec)
    global_loss = _global_loss_fn(data, loss_fn, spec)
    curve = []
    for _ in range(ROUNDS):
        state, _ = round_fn(state)
        curve.append(global_loss(state.omega))
    return np.asarray(jax.device_get(jnp.stack(curve)), np.float64)


def rounds_to_target(curve: np.ndarray, target: float) -> int:
    """First round index (1-based) whose loss-at-ω reaches the target;
    ROUNDS + 1 when the leg never gets there (gate-visible)."""
    hit = np.nonzero(curve <= target)[0]
    return int(hit[0]) + 1 if hit.size else ROUNDS + 1


def convergence_sections(report: dict, print_fn=print) -> None:
    data, params0, loss_fn = make_least_squares(
        N_CLIENTS, N_POINTS, DIM, seed=SEED)
    spec = make_flat_spec(params0)
    for rate in PARTICIPATION_GRID:
        curves = {mode: _run_leg(rate, mode, data, params0, loss_fn,
                                 spec) for mode in MODES}
        anchor = curves["none"]
        target = float(anchor[0]
                       - TARGET_DESCENT * (anchor[0] - anchor[-1]))
        for mode in MODES:
            rtt = rounds_to_target(curves[mode], target)
            report[_grid_name(rate, mode)] = {
                "participation": rate, "mode": mode,
                "rounds_to_target": rtt,
                "target_loss": target,
                "final_loss": float(curves[mode][-1]),
                "rounds_run": ROUNDS,
            }
            print_fn(f"{_grid_name(rate, mode)},{rtt},"
                     f"final_loss={curves[mode][-1]:.5f} "
                     f"target={target:.5f}")


def run(print_fn=print) -> dict:
    report: dict = {}
    wire_sections(report, print_fn)
    convergence_sections(report, print_fn)
    report["_env"] = _env_fingerprint()
    path = os.path.join(BENCH_DIR, "BENCH_comm.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    print_fn(f"wrote {path}")
    return report


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run()
