"""The ragged paged-attention kernel's share of its roofline. Attention
over a paged KV pool is bound by HBM: the least a step's kernels can do
is read every live row's keys and values once, ``live_kv_tokens`` (the
count on ``serve.step``: the sum of the rows' context lengths) x
``kv_bytes_per_token`` over the chip's bandwidth; divided by the device
time of one step's ``ragged_paged_attention`` events in the trace (a
step has one event a layer). Both are means over the same steps, the
ones the profiler saw: a step's load varies through the window."""
import statistics

from benchmark import program_log, xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_paged_attention"
ITEMSIZE = {"bfloat16": 2, "float32": 4}


def kv_bytes_per_token(cfg):
    """Keys and values of one token over every layer, as the pool holds
    them."""
    hd = cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd \
        * ITEMSIZE[cfg["dtype"]]


def read(run):
    if not run.get("trace") or not run.get("peaks"):
        return None
    w = program_log.window(run, "serve.step")
    seconds = xplane.op_seconds(run["trace"], KERNEL)
    steps = xplane.op_count(run["trace"], KERNEL) \
        / run["config"]["num_hidden_layers"]
    traced = w and w.traced_steps(run["traffic"]["trace_after_s"],
                                  round(steps))
    if not traced or not seconds:
        return None
    live = statistics.fmean(w.counts("live_kv_tokens", traced))
    floor = live * kv_bytes_per_token(run["config"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (seconds / steps)
