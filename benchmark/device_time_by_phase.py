#!/usr/bin/env python3
"""Run one cell with a trace and print the device's busy time by phase
and pass, every phase (the result line carries the ``*_dev_pct`` shares
only), in milliseconds a program step, with how much of it the table's
first-reader rule placed (instructions the compiler left without a
traced op's metadata), the largest instructions no phase owns and what
registering the step executable cost.

    python3 benchmark/device_time_by_phase.py --workload <cell> \\
        --seed <n> --seconds <s> [--out chiprun_out/<name>.json]

The cell runs as ``run.py --trace 1`` runs it (its result line is
printed too); the trace is kept, read again here, and joined with the
program's instruction -> phase table (``benchmark/device_phases.py``).
Needs the program's ``paddle_tpu/profiler/phases.py``.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "async-collective")


def breakdown(trace_dir, executable):
    from benchmark import xplane
    from paddle_tpu.jit.hlo_forensics import instruction_metadata
    from paddle_tpu.profiler import phases, spans
    events = xplane.load(xplane.find_xplane(trace_dir))
    trace = xplane.reduce(events)
    if trace is None:
        raise SystemExit("device_time_by_phase: the trace holds no device "
                         "plane (a CPU rehearsal has none)")
    name = phases.newest(executable)
    table = phases.table(name)
    charged = phases.charge(trace["op_seconds"], name)
    lo = next(e for e in events["spans"] if e[0] == "bench.trace_window")
    steps = sum(1 for e in events["spans"]
                if e[0] == executable and lo[1] <= e[1] < lo[1] + lo[2])
    busy = trace["busy_s"]
    by_phase = {}
    for (phase, which), s in charged.items():
        by_phase.setdefault(str(phase), {})[which] = s
    op_names = {n: o for n, _, o, _ in
                instruction_metadata(phases.text(name))}
    unscoped = sorted(((s, n) for n, s in trace["op_seconds"].items()
                       if table.get(n, (None,))[0] is None
                       and xplane.family(n) not in phases.CONTAINERS),
                      reverse=True)
    known = sum(s for n, s in trace["op_seconds"].items() if n in table)
    # what the first-reader rule placed (phases.parse): seconds that
    # carry a phase their own metadata did not name
    rule = phases.placed_by_reader(name)
    placed = {}
    for n, s in trace["op_seconds"].items():
        if n in rule and xplane.family(n) not in phases.CONTAINERS:
            placed[table[n][0]] = placed.get(table[n][0], 0.0) + s
    by_family = {}
    for fam, _ in xplane.top_families(trace["op_seconds"], 14):
        if fam in phases.CONTAINERS:
            continue
        split = by_family.setdefault(fam, {})
        for n, s in trace["op_seconds"].items():
            if xplane.family(n) == fam:
                key = "/".join(map(str, table.get(n, (None, "fwd"))))
                split[key] = split.get(key, 0.0) + 1e3 * s / max(steps, 1)
    out = {
        "executable": name, "steps_in_trace": steps, "busy_s": busy,
        "window_s": trace["window_s"], "chips": trace["chips"],
        "busy_ms_a_step": 1e3 * busy / max(steps, 1),
        "charged_pct_of_busy": 100 * sum(charged.values()) / busy,
        # traced names the table does not know: another executable's
        "traced_seconds_in_table_pct": 100 * known / max(
            sum(trace["op_seconds"].values()), 1e-12),
        "phases_ms_a_step": {
            p: {w: 1e3 * s / max(steps, 1) for w, s in sorted(by.items())}
            for p, by in sorted(by_phase.items())},
        "phases_pct_of_busy": {
            p: 100 * sum(by.values()) / busy
            for p, by in sorted(by_phase.items())},
        # of the above, what the rule placed: coverage by metadata
        # alone is 100 - unscoped - this
        "placed_by_reader_pct_of_busy": 100 * sum(placed.values()) / busy,
        "placed_by_reader_ms_a_step": {
            p: 1e3 * s / max(steps, 1) for p, s in sorted(placed.items())},
        "unscoped_collectives_pct_of_busy": 100 * sum(
            s for s, n in unscoped
            if any(c in n for c in COLLECTIVES)) / busy,
        "largest_unscoped": [
            {"instruction": n, "ms_a_step": 1e3 * s / max(steps, 1),
             "op_name": op_names.get(n)} for s, n in unscoped[:12]],
        "families": xplane.top_families(trace["op_seconds"], 16),
        # the unnamed families re-read: each of the largest by phase
        "families_by_phase_ms_a_step": by_family,
        "register_ms": [
            (r.t1_ns - r.t0_ns) / 1e6 for r in spans.records()
            if r.name in ("serve.register", "train.register")],
        # the host's own time in the always-on counts (ROADMAP C11)
        "assemble_spans": spans.summary(prefix="serve.assemble"),
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmark import run
    keep = tempfile.mkdtemp(prefix="phase_trace_")
    try:
        rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1",
                       "--keep-trace", keep])
        if rc:
            return rc
        traffic = run.load_cell(os.path.join(run.ROOT, "BENCHMARK.json"),
                                args.workload)[3]
        out = breakdown(keep, "train.step" if traffic["kind"] == "train_steps"
                        else "serve.step")
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    text = json.dumps(out, indent=1, default=float)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
