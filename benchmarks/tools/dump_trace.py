#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, and for each line the
event names that took most time, with the first event's stats.  Look at a
trace by hand with this before trusting ``benchmarks/trace/reduce.py``.

    python3 benchmarks/tools/dump_trace.py <trace dir or .xplane.pb> [names per line]
"""

from __future__ import annotations

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from benchmarks.trace import reduce as tr  # noqa: E402


def main() -> int:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    if not path.endswith(".pb"):
        path = tr.find_xplane(path)
    print("trace", path)
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            by_name: dict = {}
            first: dict = {}
            n = 0
            for e in line.events:
                n += 1
                by_name[e.name] = by_name.get(e.name, 0) + e.duration_ns
                if e.name not in first:
                    first[e.name] = e
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, "
                  f"{len(by_name)} names")
            for name, ns in sorted(by_name.items(),
                                   key=lambda kv: -kv[1])[:top]:
                stats = {k: (v if len(str(v)) < 90 else str(v)[:90] + "...")
                         for k, v in first[name].stats}
                print(f"    {ns * 1e-6:10.3f} ms  {name[:100]!r}  {stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
