#!/usr/bin/env python3
"""Print what a profiler trace holds, to look at one by hand before
writing against it: planes, lines, and each line's longest events.

    python3 benchmark/dump_trace.py <trace_dir or .xplane.pb> [events_json]

With a second argument, also writes the plain events ``xplane.load``
reads (what the reduction's test fixture is cut from)."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    from jax.profiler import ProfileData
    from benchmark import xplane
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            total = sum(e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{total / 1e6:.3f} ms")
            by = {}
            for e in evs:
                by[e.name] = by.get(e.name, 0) + e.duration_ns
            for name, ns in sorted(by.items(), key=lambda kv: -kv[1])[:12]:
                print(f"      {ns / 1e6:10.3f} ms  {name[:120]}")
            if evs:
                e = max(evs, key=lambda e: e.duration_ns)
                print("      stats of the longest:",
                      [(k, str(v)[:80]) for k, v in list(e.stats)[:8]])
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            json.dump(xplane.load(path), f)


if __name__ == "__main__":
    main(sys.argv)
