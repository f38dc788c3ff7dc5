"""Print what a profiler trace holds: planes, lines, event counts, and
the first and the longest events of each line with their stats.

    python bench/inspect_trace.py bench_out/<workload>-<seed>
"""
from __future__ import annotations

import os
import sys


def main(path: str, per_line: int = 4) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = [os.path.join(b, f) for b, _, fs in os.walk(path)
                 for f in fs if f.endswith(".xplane.pb")]
        path = found[0]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}")
            if not events:
                continue
            print(f"    span {min(e.start_ns for e in events):.0f} .. "
                  f"{max(e.end_ns for e in events):.0f}")
            longest = sorted(events, key=lambda e: -e.duration_ns)
            for e in events[:per_line] + longest[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats}
                print(f"    EV {e.name[:100]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}")


if __name__ == "__main__":
    main(*sys.argv[1:])
