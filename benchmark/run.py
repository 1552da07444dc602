#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration and a
traffic mix; ``configs/<config>.json`` and ``traffic/<traffic>.json``
hold them; the traffic file's ``kind`` picks the runner under
``runners/``; the cell's per-layer metrics are the readers under
``layer_metrics/`` that ``BENCHMARK.json`` lists for it. Adding a cell is
adding files and entries (README.md).

One process, which holds the chip. Without an accelerator, or with fewer
chips than the cell asks for, this exits non-zero and prints no result.
``--rehearse`` walks the same path at whatever size the files give on
any backend, and prints names but no value: a number from a CPU is not a
measurement.
"""
import argparse
import importlib
import importlib.util
import json
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Ctx:
    """What a runner is given."""

    def __init__(self, args, cell, config, traffic):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rate = bool(args.trace), args.rate
        self.keep_trace = args.keep_trace
        self.chips = cell["chips"]

    def info(self, **kw):
        """An earlier line of output: counts a reader may want."""
        print(json.dumps({"info": self.cell["name"], **kw}, default=float),
              flush=True)

    @staticmethod
    def reference(config):
        return importlib.import_module(
            f"benchmark.references.{config['reference']}")


def load_cell(bench_path, workload):
    """(benchmark, cell, configuration, traffic) of a workload name.
    Files are found from the directory that holds ``BENCHMARK.json``."""
    base = os.path.dirname(os.path.abspath(bench_path))
    with open(bench_path) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in {bench_path}; "
                         f"there are {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(base, entry["file"])) as f:
        config = json.load(f)
    for d in bench["paths"]:
        path = os.path.join(base, d, "traffic", cell["traffic"] + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return bench, cell, config, json.load(f)
    raise SystemExit(f"run.py: no traffic file {cell['traffic']}.json under "
                     f"{bench['paths']}")


def enable_compile_cache(jax):
    """JAX's persistent cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept: only a
    cell's first run in a checkout compiles."""
    from paddle_tpu.core.compile_cache import enable_compile_cache as enable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable()


def metrics_of(bench, group, workload):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def read_layer_metric(name, run):
    """The value a metric's own reader takes from the run, or None."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="requests/s instead of the traffic file's "
                         "(the sweep's, and hand runs)")
    ap.add_argument("--benchmark-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend; prints names, never a value")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to leave the profiler's files in")
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    bench, cell, config, traffic = load_cell(args.benchmark_json,
                                             args.workload)
    import jax
    devs = jax.devices()
    on_chip = devs[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"run.py: no accelerator (platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} chips, JAX "
              f"reports {len(devs)}", file=sys.stderr)
        return 2
    from benchmark import peaks, xplane
    # a CPU rehearsal has no use for the cache and knows no peaks
    cache_dir = enable_compile_cache(jax) if on_chip else None
    chip = peaks.peaks_for(devs[0].device_kind) if on_chip else None
    ctx = Ctx(args, cell, config, traffic)
    ctx.info(start=True, platform=devs[0].platform,
             kind=devs[0].device_kind, devices=len(devs), seed=args.seed,
             seconds=args.seconds, trace=args.trace, compile_cache=cache_dir)

    runner = importlib.import_module(f"benchmark.runners.{traffic['kind']}")
    out = runner.run(ctx)

    values = dict(out["end_to_end"])
    values["setup_s"] = out["t_open"] - T_START
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"]}
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devs[:cell["chips"]]]
    device["memory_peak_bytes"] = max((p for p in peak if p), default=None)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if not args.trace:
        wanted = metrics_of(bench, "end_to_end", cell["name"])
    else:
        wanted = metrics_of(bench, "per_layer", cell["name"])
        run = dict(out["run"], end_to_end=values, config=config,
                   traffic=traffic, peaks=chip, chips=cell["chips"],
                   trace=None)
        if run.get("events"):
            run["trace"] = xplane.reduce(run["events"])
        for m in wanted:
            v = read_layer_metric(m["name"], run)
            if v is not None:
                values[m["name"]] = v
        if run["trace"]:
            device["busy_s"] = run["trace"]["busy_s"]
            device["window_s"] = run["trace"]["window_s"]
            result["breakdown"] = {
                "device_ops": xplane.top_families(run["trace"]["op_seconds"]),
                "idle_gaps": xplane.top(run["trace"]["idle_seconds_by_span"]),
            }
    names = [m["name"] for m in wanted if m["name"] in values]
    # what decided ``correct``, each number beside its limit: the last
    # lines of the errors and the last key of the result
    compared = {k: {"value": v, "limit": limit}
                for k, (v, limit) in out["compared"].items()}
    for k, c in compared.items():
        print(f"compared {k}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    if args.rehearse or not on_chip:
        print(json.dumps({"rehearsal": cell["name"], "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], "would_report": names,
                          "compared": sorted(compared)}))
        return 0
    result["metrics"] = {k: {"value": values[k], "unit": units[k]}
                         for k in names}
    result["device"] = device
    result["compared"] = compared
    print(json.dumps(result, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
