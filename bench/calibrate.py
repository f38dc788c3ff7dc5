"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out <file.json>]

For every seed: the program's first rounds (ticks) from the seed
through the window's own call, against the reference's; these are the
sound runs.  For every control seed also: the control (the reference
in bfloat16 in the program's place; ``high`` where the configuration runs at
"highest") and the planted faults
(``half_batch``, ``flip_event``: the reference with that fault in the
program's place), each against the reference.  All in one process, at
the cell's own size; no measured window.  Prints one JSON line per
reading and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import gc
import json
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="allow the CPU (a rehearsal; readings are not "
                         "the chip's)")
    ap.add_argument("--set", action="append", default=[],
                    help="key=json: override a configuration key")
    ap.add_argument("--traffic", action="append", default=[],
                    help="key=json: override a traffic key")
    args = ap.parse_args(argv)

    import harness

    cell = harness.resolve(args.workload)
    for item in args.set:
        key, value = item.split("=", 1)
        cell.cfg[key] = json.loads(value)
    for item in args.traffic:
        key, value = item.split("=", 1)
        cell.traffic[key] = json.loads(value)
    harness.setup_jax(not args.cpu, cell.chips)
    harness.set_precision(cell.cfg)
    harness.import_program()

    from arrivals import make_arrivals
    from repro.core import init_state, make_round_fn

    serving = cell.traffic["kind"] == "serve"
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        problem = harness.build_problem(cell, seed)
        state = init_state(problem.fl, problem.params0, spec=problem.spec)
        round_fn = make_round_fn(problem.fl, problem.loss_fn, problem.data,
                                 spec=problem.spec, ragged=problem.ragged,
                                 arrivals_arg=serving)
        arrivals = None
        if serving:
            trace = make_arrivals(cell.traffic["arrivals"],
                                  cell.cfg["n_clients"],
                                  cell.traffic["check_ticks"], seed)
            state, pm, ps, _ = harness.first_ticks(cell, round_fn, state,
                                                   trace)
            arrivals = trace
        else:
            state, pm, ps = harness.first_rounds(cell, round_fn, state)
        del state, round_fn
        gc.collect()
        variants = [("program", {})]
        if seed in controls:
            ctl = harness.control(cell)
            name = "control_high" if "precision" in ctl else "control_bf16"
            variants += [(name, ctl),
                         ("fault_half_batch", {"fault": "half_batch"}),
                         ("fault_flip_event", {"fault": "flip_event"})]
        for name, kw in variants:
            nums = harness.compare(cell, problem, seed, pm, ps, arrivals,
                                   diagnostics=True, **kw)
            row = {"workload": args.workload, "seed": seed, "run": name,
                   **nums,
                   "events": [int(m["events"].sum()) for m in pm],
                   "committed": [int(m["committed"].sum()) for m in pm]}
            rows.append(row)
            print(json.dumps(row, default=float), flush=True)
        print(f"seed {seed} took {time.perf_counter() - t:.1f} s",
              flush=True)
        del problem
        gc.collect()
    summary = {}
    for name in sorted({r["run"] for r in rows}):
        sel = [r for r in rows if r["run"] == name]
        summary[name] = {k: [float(np.min([r[k] for r in sel])),
                             float(np.max([r[k] for r in sel]))]
                         for k in sel[0]
                         if k.endswith(("_gap", "_diff", "_median"))}
    print(json.dumps({"summary_min_max": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"rows": rows, "summary_min_max": summary}, f,
                      indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
