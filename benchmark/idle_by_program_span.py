#!/usr/bin/env python3
"""Print, for a kept trace, the device's idle time by what the host was
doing, down to the program's own spans, as a table with each span's
share and its milliseconds a program step.

    python3 benchmark/run.py --workload <cell> --trace 1 --keep-trace <dir>
    python3 benchmark/idle_by_program_span.py <dir or .xplane.pb>

The attribution is ``xplane.reduce``'s, the one behind the result
line's ``breakdown.idle_gaps`` (each part of a gap to the innermost
covering span); that line keeps the ten largest in seconds, this prints
all of them a step.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    from benchmark import xplane
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    events = xplane.load(path)
    got = xplane.reduce(events)
    if got is None:
        print(f"no bench.trace_window span or no device plane in {path}")
        return 1
    by = got["idle_seconds_by_span"]
    window_s, idle_s = got["window_s"], got["window_s"] - got["busy_s"]
    steps = sum(1 for e in events["spans"]
                if e[0] in ("serve.step", "train.step"))
    print(f"window {window_s:.4f} s, device idle {idle_s:.4f} s "
          f"({got['idle_pct']:.2f} %), {steps} program steps")
    print(f"{'innermost span':<24}{'idle s':>10}{'share %':>9}"
          f"{'ms a step':>11}")
    for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
        per = f"{1e3 * s / steps:>11.3f}" if steps else f"{'':>11}"
        print(f"{name:<24}{s:>10.4f}{100 * s / idle_s:>9.1f}{per}")
    # what the program's spans leave unnamed: under the benchmark's span
    # around the call into the program and nothing deeper, or no span
    alone = by.get("bench.step", 0.0) + by.get(xplane.NO_SPAN, 0.0)
    print(f"on bench.step alone or {xplane.NO_SPAN}: "
          f"{100 * alone / idle_s:.1f} % of the idle time")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
