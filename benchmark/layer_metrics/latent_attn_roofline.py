"""The latent attention kernel's share of its roofline. The least a
step's ``ragged_latent_attention`` kernels can take
(``benchmark/mla_costs.py``) is the larger of the FLOPs of its query-key
pairs (``attn_qk_pairs``, the count on ``serve.step``: over layers, rows
and the step's query tokens, the keys each sees; ``2 x heads x (row +
value width)`` FLOPs a pair) over the chip's bf16 peak, and every live
token's row read once a layer (``live_kv_tokens`` x layers x the row's
bytes, unpadded) over its bandwidth; divided by the device time of one
step's kernel events (one a layer). Both are means over the steps the
profiler saw."""
from benchmark import mla_costs, moe_costs

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_latent_attention"


def read(run):
    cfg = run.get("config") or {}
    if "kv_lora_rank" not in cfg:
        return None
    got = moe_costs.kernel_steps(run, KERNEL, cfg["num_hidden_layers"],
                                 "attn_qk_pairs", "live_kv_tokens")
    if not got:
        return None
    seconds, pairs, live = got
    floor = mla_costs.attention_floor_s(cfg, run["peaks"], pairs, live)
    # a program from before the count reads 0 pairs: nothing to report
    return 100.0 * floor / seconds if pairs else None
