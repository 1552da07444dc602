"""The routed experts' grouped matrix products' share of their roofline.
The least a step's ``grouped_matmul`` kernels can take is the larger of
two floors: the bytes they have to move (every held expert that got a
token, its three matrices once, and the pairs' rows in and out) over the
chip's bandwidth, and the pairs' FLOPs over its bf16 peak
(``benchmark/moe_costs.py``); divided by the device time of one step's
``grouped_matmul`` events (three a sparse layer). ``moe_experts_touched``
and ``moe_pairs_held`` are counts on ``serve.step``, summed over the
step's sparse layers; both sides are means over the steps the profiler
saw."""
from benchmark import moe_costs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "grouped_matmul"


def read(run):
    cfg = run.get("config") or {}
    if "mlp_layer_types" not in cfg:
        return None
    got = moe_costs.kernel_steps(
        run, KERNEL, 3 * moe_costs.sparse_layers(cfg),
        "moe_experts_touched", "moe_pairs_held")
    if not got:
        return None
    seconds, touched, pairs = got
    floor = max(moe_costs.expert_bytes(cfg, touched, pairs)
                / run["peaks"]["hbm_bytes_per_s"],
                moe_costs.expert_flops(cfg, pairs)
                / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * floor / seconds if floor else None
