"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cell's pieces are found by name from
``BENCHMARK.json`` (see ``bench/harness.py``).  Set-up phases and the
window are printed first; the last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number of the comparison with its limit, which also end standard error.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def _plain(value):
    if hasattr(value, "item"):
        return value.item()
    raise TypeError(f"not JSON: {type(value)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import BenchError, run

    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_start=T_START)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, row in result["checks"].items():
        print(f"check {name} = {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result, default=_plain), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
