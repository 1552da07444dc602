#!/usr/bin/env python3
"""From repeated runs of a cell to the spread its bounds stand on.

    python3 benchmark/spread.py <runs.jsonl>

Each line of the file is ``{"set": 1|2, "seed": n, "result": <the last
line of a run>}``. For every metric: each set's spread (distance between
first and third quartile by ``statistics.quantiles(values, n=4)``, as a
share of the median), the wider of the two, five times it (the bound to
set, never under 1 %), and how far the second set's median lies from the
first's. A set's first run compiled, so ``setup_s`` leaves it out.
"""
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv):
    sets = {}
    with open(argv[1]) as f:
        for line in f:
            row = json.loads(line)
            for name, m in row["result"]["metrics"].items():
                sets.setdefault(name, {}).setdefault(row["set"], []).append(
                    m["value"])
    for name, by_set in sorted(sets.items()):
        if name == "setup_s":
            by_set = {k: v[1:] for k, v in by_set.items()}
        spreads = {k: spread(v) for k, v in by_set.items() if len(v) >= 2}
        medians = {k: statistics.median(v) for k, v in by_set.items()}
        widest = max(spreads.values())
        keys = sorted(medians)
        drift = (medians[keys[-1]] - medians[keys[0]]) / medians[keys[0]]
        print(json.dumps({
            "metric": name, "medians": medians, "spreads": spreads,
            "widest_spread": widest, "five_times": max(0.01, 5 * widest),
            "second_median_vs_first": drift,
            "values": by_set}))


if __name__ == "__main__":
    main(sys.argv)
