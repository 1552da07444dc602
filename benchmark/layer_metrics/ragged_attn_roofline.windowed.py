"""The ragged paged-attention kernel's share of its roofline where
layers keep different amounts of KV. ``ragged_attn_roofline``'s floor is
every live token in every layer; a window layer has to read only a row's
last ``window`` keys and its chunk, so the floor here is
``attn_kv_tokens_live`` (the count on ``serve.step``: over layers and
rows, ``min(kv_len, window + q_len)``, all of ``kv_len`` on a full layer)
x one layer's bytes a token over the chip's bandwidth; divided by the
device time of one step's ``ragged_paged_attention`` events (one a
layer). Both are means over the steps the profiler saw."""
from benchmark import moe_costs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_paged_attention"


def read(run):
    cfg = run.get("config") or {}
    got = moe_costs.kernel_steps(run, KERNEL, cfg.get("num_hidden_layers"),
                                 "attn_kv_tokens_live")
    if not got:
        return None
    seconds, live = got
    floor = live * moe_costs.kv_bytes_per_token_per_layer(cfg) \
        / run["peaks"]["hbm_bytes_per_s"]
    # a program from before the count reads 0: nothing to report
    return 100.0 * floor / seconds if floor else None
