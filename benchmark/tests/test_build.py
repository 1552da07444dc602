"""``build.build_model`` knows no architecture: the config class says
what of a published file its model would drop, and a configuration of
another shape than Llama's enters as files."""
import dataclasses
import json
import os
import types

import pytest

from benchmark import build, published

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LLAMA = "paddle_tpu.models.LlamaConfig"
PADDLE = types.SimpleNamespace(seed=lambda n: None)


@dataclasses.dataclass
class WindowMoeConfig:
    """Stands for a config class of the program whose model has window
    layers, heads wider than hidden / heads, and experts."""
    hidden_size: int = 64
    num_attention_heads: int = 4
    head_dim: int = 16
    sliding_window: int = None
    layer_types: list = None
    num_experts: int = 0

    @classmethod
    def check_published(cls, cfg):
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is set; WindowMoe drops it")


@dataclasses.dataclass
class SilentConfig:
    hidden_size: int = 64


class KeepsItsConfig:
    def __init__(self, config):
        self.config = config


def _file(config_class, **keys):
    """A configuration file's dict at the drawn shape: a 128-token
    window on three layers of four, 64 heads of 128 on a hidden size of
    6144, experts."""
    cfg = {"model": {"class": f"{__name__}.KeepsItsConfig",
                     "config_class": config_class},
           "hidden_size": 6144, "num_attention_heads": 64, "head_dim": 128,
           "sliding_window": 128, "num_experts": 16,
           "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
           "source": "a test", "vocab_size": 512}
    cfg.update(keys)
    return cfg


def test_a_configuration_of_another_shape_is_files_only():
    cfg = _file(f"{__name__}.WindowMoeConfig")
    model = build.build_model(PADDLE, cfg, 3000000001)
    got = model.config
    assert isinstance(model, KeepsItsConfig)
    assert (got.sliding_window, got.head_dim, got.num_attention_heads,
            got.hidden_size, got.num_experts) == (128, 128, 64, 6144, 16)
    assert got.head_dim * got.num_attention_heads != got.hidden_size
    assert got.layer_types == cfg["layer_types"]
    # the class's own rule is asked, with the whole file
    with pytest.raises(ValueError, match="rope_scaling"):
        build.build_model(PADDLE, dict(cfg, rope_scaling={"factor": 8}), 1)


@pytest.mark.parametrize("keep, named", [
    ("sliding_window", "sliding_window"), ("head_dim", "head_dim")])
def test_the_same_file_under_the_llama_class_is_still_refused(keep, named):
    cfg = _file(LLAMA, num_key_value_heads=8)
    if keep != "sliding_window":
        cfg["sliding_window"] = None
    if keep != "head_dim":
        del cfg["head_dim"]
    with pytest.raises(ValueError, match=named):
        build.build_model(PADDLE, cfg, 1)


def test_a_class_without_the_check_is_refused_by_name():
    with pytest.raises(ValueError) as e:
        build.build_model(PADDLE, _file(f"{__name__}.SilentConfig"), 1)
    assert "SilentConfig" in str(e.value)
    assert "check_published" in str(e.value)


def test_the_class_is_asked_before_the_benchmarks_table(monkeypatch):
    monkeypatch.setitem(published.CHECKS,
                        f"{__name__}.WindowMoeConfig", published.llama)
    assert build.published_check(WindowMoeConfig).__func__ is \
        WindowMoeConfig.check_published.__func__


def test_a_subclass_inherits_its_bases_rules():
    from paddle_tpu.models.llama_moe import LlamaMoeConfig
    assert build.published_check(LlamaMoeConfig) is published.llama


# --- the Llama class's rules -------------------------------------------------

MISTRAL_PUBLISHED = {"hidden_act": "silu", "hidden_size": 4096,
                     "num_attention_heads": 32, "head_dim": 128,
                     "sliding_window": None}


@pytest.mark.parametrize("keys, named", [
    ({"sliding_window": 4096}, "sliding_window"),
    ({"hidden_act": "gelu_pytorch_tanh"}, "hidden_act"),
    ({"head_dim": 256}, "head_dim"),
    ({"rope_scaling": {"rope_type": "llama3", "factor": 32.0}},
     "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
])
def test_llama_refuses_what_its_model_would_drop(keys, named):
    with pytest.raises(ValueError, match=named):
        published.llama(dict(MISTRAL_PUBLISHED, **keys))


def test_llama_names_every_dropped_setting_at_once():
    with pytest.raises(ValueError) as e:
        published.llama(dict(MISTRAL_PUBLISHED, sliding_window=128,
                             head_dim=64, mlp_bias=True))
    for named in ("sliding_window", "head_dim", "mlp_bias"):
        assert named in str(e.value)


def test_llama_passes_published_keys_it_honours():
    published.llama(MISTRAL_PUBLISHED)
    # SmolLM2's config.json states these, all at what the model does
    published.llama({"hidden_act": "silu", "hidden_size": 2048,
                     "num_attention_heads": 32, "rope_scaling": None,
                     "attention_bias": False, "mlp_bias": False})


@pytest.mark.parametrize("path", [
    "benchmark/configs/mistral-7b.json",
    "benchmark/configs/smollm2-1.7b.json",
    "benchmark/tests/toy/configs/toy-serve.json",
    "benchmark/tests/toy/configs/toy-train.json"])
def test_the_benchmarks_own_files_pass_their_class(path):
    with open(os.path.join(ROOT, path)) as f:
        cfg = json.load(f)
    config_cls = build.load_attr(cfg["model"]["config_class"])
    build.published_check(config_cls)(cfg)
