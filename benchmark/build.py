"""From a configuration file to the program's model. The file names the
classes; nothing here knows a configuration by name."""
import dataclasses
import importlib


def load_attr(dotted):
    module, _, name = dotted.rpartition(".")
    return getattr(importlib.import_module(module), name)


def check_supported(cfg):
    """Refuse a published setting the Llama-style path would silently
    drop: a wrong model under a real name is worse than none."""
    if cfg.get("sliding_window") is not None:
        raise ValueError("sliding_window is set; this path attends fully")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {cfg['hidden_act']!r} is not SwiGLU's")
    hd = cfg.get("head_dim")
    if hd is not None and hd * cfg["num_attention_heads"] \
            != cfg["hidden_size"]:
        raise ValueError("head_dim x heads differs from hidden_size")


def build_model(paddle, cfg, seed, **extra):
    """The model a configuration file describes, weights from ``seed``
    through the program's own initialiser."""
    check_supported(cfg)
    model_cls = load_attr(cfg["model"]["class"])
    config_cls = load_attr(cfg["model"]["config_class"])
    fields = {f.name for f in dataclasses.fields(config_cls)}
    kw = {k: v for k, v in cfg.items() if k in fields}
    kw.update(extra)
    paddle.seed(int(seed) % (2 ** 31 - 1))
    return model_cls(config_cls(**kw))
