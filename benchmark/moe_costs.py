"""Operation and byte counts of a routed expert layer's grouped matrix
products, and of a decoder whose layers keep different amounts of KV:
what the shares of a roofline of such a configuration are taken against.
(``peaks.py`` holds the chips' peaks and the dense decoder's counts.)"""

import statistics

from . import program_log, xplane

ITEMSIZE = {"bfloat16": 2, "float32": 4}
SLIDING = "sliding_attention"


def expert_bytes(cfg, experts_touched, pairs):
    """HBM bytes the three grouped products of routed layers have to
    move: the gate, up and down matrices of every held expert with at
    least one token, once (``experts_touched``, summed over layers),
    and each token-expert pair's rows in and out of each product:
    hidden in and width out twice, width in and hidden out once."""
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    b = ITEMSIZE[cfg["dtype"]]
    return experts_touched * 3 * h * m * b + pairs * 3 * (h + m) * b


def expert_flops(cfg, pairs):
    """FLOPs of one token-expert pair through a SwiGLU expert: three
    products of hidden x width, a multiply and an add each."""
    return pairs * 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_layers(cfg):
    return cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count("sparse")


def kv_bytes_per_token_per_layer(cfg):
    """Keys and values of one token in one layer, as the pool holds
    them."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] \
        * ITEMSIZE[cfg["dtype"]]


def page_bytes(cfg):
    """(bytes of a full-group page, bytes of a window-group page): a
    page of ``engine.page_size`` tokens over the group's layers."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    window = types.count(SLIDING)
    per = kv_bytes_per_token_per_layer(cfg) * cfg["engine"]["page_size"]
    return (len(types) - window) * per, window * per


def kernel_steps(run, kernel, calls_a_step, *counts):
    """For a share of a kernel's roofline: (device seconds of ``kernel``
    a step, mean of each of the ``serve.step`` counts named), both over
    the steps the profiler saw; None where the trace or the program's
    log has nothing to read. ``calls_a_step`` tells the steps from the
    kernel's events."""
    if not run.get("trace") or not run.get("peaks"):
        return None
    w = program_log.window(run, "serve.step")
    seconds = xplane.op_seconds(run["trace"], kernel)
    if not w or not seconds:
        return None
    steps = xplane.op_count(run["trace"], kernel) / calls_a_step
    traced = w.traced_steps(run["traffic"]["trace_after_s"], round(steps))
    if not traced:
        return None
    return (seconds / steps,
            *(statistics.fmean(w.counts(c, traced)) for c in counts))
