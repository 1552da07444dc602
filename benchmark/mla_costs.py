"""Operation and byte counts of latent (MLA) attention over a latent
paged cache: what ``latent_attn_roofline`` and
``latent_kv_bytes_per_token`` are taken against. (``moe_costs.py`` holds
the routed experts' counts and the step-matching of a kernel's time,
``peaks.py`` the chips' peaks.)"""

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def latent_width(cfg):
    """Values of the row a token caches a layer: the compressed keys and
    values and the one rotary key, ``kv_lora_rank + qk_rope_head_dim``."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def row_bytes(cfg):
    """Bytes of that row as the mathematics needs them (1,152 in bf16 at
    512 + 64): the roofline's floor reads a live token's row once a
    layer, and no padding."""
    return latent_width(cfg) * ITEMSIZE[cfg["dtype"]]


def flops_per_pair(cfg):
    """FLOPs of one query token against one key in the absorbed form,
    all heads: the score over the whole row (``kv_lora_rank +
    qk_rope_head_dim``) and the weighted sum over its first
    ``kv_lora_rank`` values, a multiply and an add each."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["kv_lora_rank"])


def attention_floor_s(cfg, peaks, qk_pairs, live_kv_tokens):
    """The least a step's latent attention can take: the larger of its
    FLOPs (``qk_pairs``: over layers, rows and query tokens, the keys
    each sees) over the bf16 peak, and every live token's row read once
    a layer over the bandwidth."""
    return max(qk_pairs * flops_per_pair(cfg) / peaks["bf16_flops_per_s"],
               live_kv_tokens * cfg["num_hidden_layers"] * row_bytes(cfg)
               / peaks["hbm_bytes_per_s"])
