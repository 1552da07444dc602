#!/usr/bin/env python3
"""The controls of a Qwen3-Next serving cell's ``correct``, run once on
the chip: each is a deliberately wrong program or reference, judged by the
runner's own comparison (``Served.check`` against the configuration's
``logit_tol``). Not part of a check: the readings go, by hand, into the
configuration's ``logit_tol_why``.

    python3 benchmark/qwen3_next_controls.py --workload <cell> --seed <n> \\
        --seconds 45 --controls int8_weights,gate_dropped,g_zero \\
        [--out chiprun_out/controls.json]

One engine over the cell's model and one window at the cell's rate. A
PROGRAM control (``bf16_state``) is applied before the engine is built,
and the window is judged by the true reference. Each REFERENCE control
(``int8_weights``, ``gate_dropped``, ``g_zero``) judges the untampered
program's window with a copy of the reference tampered so; give program
and reference controls in separate runs. Prints one JSON line a control.

- ``bf16_state``: the program keeps every linear layer's recurrent state
  in bfloat16 between steps (the kernel's state rounded with
  ``lax.reduce_precision``: a round trip through ``astype`` is dropped by
  the chip's compiler), where the configuration states float32;
- ``int8_weights``: every layer matmul's weights (in-projections and
  ``out_proj``, q / k / v / o, the router, routed and shared experts and
  the shared expert's gate) rounded to int8 per output channel;
- ``gate_dropped``: attention's output gate taken out;
- ``g_zero``: no decay in the linear layers (``A_log`` = -inf, so ``g`` =
  0).
"""
import argparse
import gc
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
PROGRAM = ("bf16_state",)
REFERENCE = ("int8_weights", "gate_dropped", "g_zero")
#: the mixers' matmuls among a layer's weights, by the reference's own
#: names (the feed-forward's are rounded where they are used)
MIXER = ("qkvz", "ba", "out", "q", "k", "v", "o")


def keep_state_in_bf16():
    """The program's linear layers keep their state in bfloat16."""
    import jax
    from paddle_tpu.kernels import gated_delta
    plain = gated_delta.ragged_gated_delta

    def rounded(*args, **kwargs):
        o, state = plain(*args, **kwargs)
        return o, jax.lax.reduce_precision(state, exponent_bits=8,
                                           mantissa_bits=7)
    gated_delta.ragged_gated_delta = rounded


def int8(w):
    """``w`` [..., in, out] rounded to int8 with one scale an output
    column, back in its own dtype."""
    import jax.numpy as jnp
    x = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=-2, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (jnp.clip(jnp.round(x / scale), -127, 127) * scale).astype(w.dtype)


def tampered_reference(config, control):
    """A fresh copy of the cell's reference module with ``control``
    applied (the copy's jitted functions trace the tampered code)."""
    import jax.numpy as jnp
    name = config["reference"]
    spec = importlib.util.spec_from_file_location(
        f"control_{control}_{name}", os.path.join(HERE, "references",
                                                  name + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    if control == "gate_dropped":
        ref._gate = jnp.ones_like
        return ref
    plain_weights = ref.weights

    def weights(model):
        w = plain_weights(model)
        if control == "g_zero":
            w["layers"] = [dict(lw, A_log=jnp.full_like(lw["A_log"], -jnp.inf))
                           if "A_log" in lw else lw for lw in w["layers"]]
        return w
    ref.weights = weights
    if control != "int8_weights":
        return ref
    # rounded a layer (an expert) at a time, as the reference uses them:
    # a rounded copy of every weight at once would not fit beside the model
    attention, linear, swiglu, gates = (ref._attention, ref._linear,
                                        ref._swiglu, ref._gates)

    def rounded(lw):
        return {k: int8(v) if k in MIXER else v for k, v in lw.items()}
    ref._attention = lambda lw, h, **kw: attention(rounded(lw), h, **kw)
    ref._linear = lambda lw, h, n, **kw: linear(rounded(lw), h, n, **kw)
    ref._swiglu = lambda wg, wu, wd, x, s: swiglu(int8(wg), int8(wu),
                                                  int8(wd), x, s)
    ref._gates = lambda router, sg, x, **kw: gates(int8(router), int8(sg), x,
                                                   **kw)
    return ref


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--controls", required=True)
    ap.add_argument("--benchmark-json",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    controls = args.controls.split(",")
    unknown = set(controls) - set(PROGRAM) - set(REFERENCE)
    if unknown:
        raise SystemExit(f"no control {sorted(unknown)}; there are "
                         f"{list(PROGRAM) + list(REFERENCE)}")
    program = [c for c in controls if c in PROGRAM]
    if program and len(controls) > 1:
        raise SystemExit("a program control runs alone: its window is "
                         "not the program's")
    sys.path.insert(0, ROOT)
    from benchmark import run as harness
    from benchmark.runners import serve_open_loop

    bench, cell, config, traffic = harness.load_cell(args.benchmark_json,
                                                     args.workload)
    import jax
    if jax.devices()[0].platform == "tpu":
        harness.enable_compile_cache(jax)
    if program:
        keep_state_in_bf16()
    ns = argparse.Namespace(seed=args.seed, seconds=args.seconds, trace=0,
                            rate=None, keep_trace=None)
    served = serve_open_loop.Served(harness.Ctx(ns, cell, config, traffic))
    raw = served.measure(args.seed, args.seconds)
    served.engine = None             # its pool, for the reference's room
    gc.collect()
    rows = []
    for control in controls:
        ref = harness.Ctx.reference(config) if control in PROGRAM \
            else tampered_reference(config, control)
        ok, detail = served.check(raw, ref)
        row = {"control": control, "kind": "program" if control in PROGRAM
               else "reference", "workload": args.workload,
               "seed": args.seed, "rate_rps": traffic["rate_rps"],
               "correct": bool(ok), **detail}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind, "rows": rows},
                      f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
