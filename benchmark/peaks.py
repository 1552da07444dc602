"""Peaks of the chips the benchmark may run on, and the operation and
byte counts that roofline shares and MFU are taken against.

One table, keyed by ``jax.Device.device_kind``. A kind that is not in it
is an error, never a default.
"""

#: Google Cloud documentation, "TPU v5e" (system architecture page): one
#: chip has 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; add a "
            f"row to benchmark/peaks.py with its source") from None


def dense_decoder_params(cfg):
    """Parameters of a Llama-style dense decoder as the step holds them:
    a tied head is the embedding, counted once."""
    h, ffn, L = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    layer = h * q + 2 * h * kv + q * h + 3 * h * ffn + 2 * h
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else embed
    return L * layer + embed + head + h


def train_useful_flops_per_token(cfg, seq_len):
    """FLOPs the forward and backward passes REQUIRE per trained token.

    6 x parameters (a tied head's matmul is real work, and the tied
    matrix is counted once as a parameter, so the embedding's rows stand
    in for it) + 12 * L * h * s for attention (QK^T and PV, forward and
    backward, causal not halved). Recomputation under remat is not
    useful work and is not counted.
    """
    attn = 12 * cfg["num_hidden_layers"] * cfg["hidden_size"] * seq_len
    return 6 * dense_decoder_params(cfg) + attn


def adamw_bytes(n_params, bytes_per_value=4):
    """HBM bytes one fused AdamW update has to move: p, g, m, v read and
    p, m, v written, all in f32 here (7 x 4 B = 28 B a parameter)."""
    return 7 * bytes_per_value * n_params
