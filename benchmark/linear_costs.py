"""Operation and byte counts of a Gated DeltaNet layer's recurrence over
state slots (``kernels/gated_delta.py``): what ``linear_attn_roofline``
is taken against. (``moe_costs.py`` holds the step-matching of a
kernel's time, ``peaks.py`` the chips' peaks.)"""

F32 = 4


def linear_layers(cfg):
    """Layers run that are linear: every one but each
    ``full_attention_interval``-th."""
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return sum(1 for i in range(n) if (i + 1) % every)


def state_bytes(cfg):
    """One row's recurrent state in one layer, float32: read once and
    written once a step."""
    return cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"] * F32


def token_bytes(cfg):
    """What one token of one layer brings in and takes out, float32 as
    the kernel is handed it: q and k a key head, v a value head, g and
    beta a value head in, o a value head out."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return F32 * (2 * hk * dk + 2 * hv * dv + 2 * hv)


def token_flops(cfg):
    """FLOPs of the recurrence for one token of one layer: per value
    head the decay, ``S^T k``, the rank-one update and ``S^T q`` over
    ``dk x dv``, eight operations an entry."""
    return cfg["linear_num_value_heads"] * 8 * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def recurrence_floor_s(cfg, peaks, rows, tokens):
    """The least a step's kernels can take: the larger of every live
    row's state read and written once a layer plus its tokens' bytes
    (``rows``, ``tokens``: summed over the linear layers) over the
    bandwidth, and the tokens' FLOPs over the bf16 peak."""
    return max((rows * 2 * state_bytes(cfg) + tokens * token_bytes(cfg))
               / peaks["hbm_bytes_per_s"],
               tokens * token_flops(cfg) / peaks["bf16_flops_per_s"])
