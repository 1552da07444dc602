#!/usr/bin/env python3
"""Find the highest arrival rate a serving cell sustains, once, on the
chip. Not part of a check: the rate found is written by hand into the
cell's traffic file (``rate_rps`` = 0.8 x the knee for a cell below it).

    python3 benchmark/sweep.py --workload <cell> --rates 0.4,0.6,0.8,1.0,1.2 \
        --seeds 1,2 --seconds 45 [--out chiprun_out/sweep.json]

One process builds the engine once and runs warm phase + window for each
rate and seed, draining in between. A rate is SUSTAINED when, on every
seed, the waiting queue at the window's end is no deeper than at its
middle. Prints one JSON line per run and a last line naming the knee.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="1,2")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--benchmark-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark import run as harness
    from benchmark.runners import serve_open_loop

    bench, cell, config, traffic = harness.load_cell(args.benchmark_json,
                                                     args.workload)
    import jax
    if jax.devices()[0].platform == "tpu":
        harness.enable_compile_cache(jax)
    ns = argparse.Namespace(seed=0, seconds=args.seconds, trace=0, rate=None,
                            keep_trace=None)
    served = serve_open_loop.Served(harness.Ctx(ns, cell, config, traffic))
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            raw = served.measure(seed, args.seconds, rate)
            s = serve_open_loop.summarize(raw)
            _, margins = served.check(raw, harness.Ctx.reference(config))
            row = {"rate_rps": rate, "seed": seed, **s["end_to_end"],
                   **s["counts"], **margins}
            row["sustained"] = row["waiting_end"] <= row["waiting_mid"]
            rows.append(row)
            print(json.dumps(row), flush=True)
            served.drain()
    ok = sorted({r["rate_rps"] for r in rows} - {r["rate_rps"] for r in rows
                                                if not r["sustained"]})
    result = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "sustained_rates": ok, "knee_rps": max(ok) if ok else None,
              "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
