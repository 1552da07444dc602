"""The gated-delta kernel's share of its roofline. The least a step's
``ragged_gated_delta`` kernels can take (``benchmark/linear_costs.py``)
is the larger of two floors: every live row's float32 state read and
written once a layer (``linear_rows``, the count on ``serve.step``, rows
with a live token summed over the linear layers) plus the step's tokens
in and out (``linear_tokens``) over the chip's bandwidth, and the
tokens' FLOPs over its bf16 peak; divided by the device time of one
step's kernel events (one a linear layer). Both are means over the steps
the profiler saw."""
from benchmark import linear_costs, moe_costs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_gated_delta"


def read(run):
    cfg = run.get("config") or {}
    if "linear_num_value_heads" not in cfg:
        return None
    got = moe_costs.kernel_steps(run, KERNEL, linear_costs.linear_layers(cfg),
                                 "linear_rows", "linear_tokens")
    if not got:
        return None
    seconds, rows, tokens = got
    floor = linear_costs.recurrence_floor_s(cfg, run["peaks"], rows, tokens)
    return 100.0 * floor / seconds if floor else None
