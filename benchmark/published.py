"""``check_published`` of the program's config classes that do not carry
their own yet: what each class's model would silently drop of a
published ``config.json``.

``build.published_check`` asks the class first; this table is the
stand-in for a class of the program that predates the rule, and loses
its row when the class grows the method (PERF.md, Open questions). A new
architecture brings ``check_published`` on its own config class and adds
nothing here.
"""


def llama(cfg):
    """``paddle_tpu.models.LlamaConfig``: full causal attention with
    heads of ``hidden_size / num_attention_heads``, plain RoPE, SwiGLU,
    no bias anywhere."""
    dropped = []
    if cfg.get("sliding_window") is not None:
        dropped.append("sliding_window is set; this path attends fully")
    if cfg.get("hidden_act", "silu") != "silu":
        dropped.append(f"hidden_act {cfg['hidden_act']!r} is not SwiGLU's")
    hd = cfg.get("head_dim")
    if hd is not None and hd * cfg["num_attention_heads"] \
            != cfg["hidden_size"]:
        dropped.append("head_dim x heads differs from hidden_size")
    if cfg.get("rope_scaling") is not None:
        dropped.append("rope_scaling is set; this path rotates by "
                       "rope_theta alone")
    for key in ("attention_bias", "mlp_bias"):
        if cfg.get(key):
            dropped.append(f"{key} is true; this path's projections "
                           f"have no bias")
    if dropped:
        raise ValueError("LlamaConfig would drop: " + "; ".join(dropped))


CHECKS = {"paddle_tpu.models.llama.LlamaConfig": llama}
