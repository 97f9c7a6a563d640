"""Print what a profiler trace holds: its planes and lines, and per device
line the ops with the most time, with their stats. For reading a trace by
hand before writing a reduction against it.

    python3 bench/trace_dump.py <trace dir or .xplane.pb> [top]
"""
from __future__ import annotations

import collections
import os
import sys


def main() -> int:
    from jax.profiler import ProfileData
    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 40
    if os.path.isdir(path):
        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from bench import devtrace
        path = devtrace.find_xplane(path)
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device:") and line.name != "python":
                continue
            acc = collections.defaultdict(lambda: [0, 0.0, None])
            for ev in evs:
                a = acc[ev.name]
                a[0] += 1
                a[1] += ev.duration_ns * 1e-9
                if a[2] is None:
                    a[2] = dict(ev.stats)
            for name, (n, s, st) in sorted(acc.items(), key=lambda kv: -kv[1][1])[:top]:
                print(f"    {s:10.6f} s {n:6d}x {name}  {st}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
