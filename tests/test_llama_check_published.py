"""``LlamaConfig.check_published``: what the Llama model would silently
drop of a published ``config.json`` is refused by name (the rules the
benchmark's stand-in table held, now on the class they guard)."""
import pytest

from paddle_tpu.models import LlamaConfig

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
        LlamaConfig.check_published(dict(MISTRAL_PUBLISHED, **keys))


def test_llama_names_every_dropped_setting_at_once():
    with pytest.raises(ValueError) as e:
        LlamaConfig.check_published(dict(
            MISTRAL_PUBLISHED, sliding_window=128, head_dim=64,
            mlp_bias=True))
    for named in ("sliding_window", "head_dim", "mlp_bias"):
        assert named in str(e.value)


@pytest.mark.parametrize("cfg", [
    MISTRAL_PUBLISHED,
    # SmolLM2's config.json states these, all at what the model does
    {"hidden_act": "silu", "hidden_size": 2048, "num_attention_heads": 32,
     "rope_scaling": None, "attention_bias": False, "mlp_bias": False},
], ids=["mistral-7b", "smollm2-1.7b"])
def test_llama_passes_published_keys_it_honours(cfg):
    LlamaConfig.check_published(cfg)


def test_a_file_of_another_shape_is_refused_under_the_llama_class():
    """A 128-token window on 64 heads of 128 over a hidden size of 6144:
    the Llama class names both, whichever comes alone."""
    wide = {"hidden_size": 6144, "num_attention_heads": 64, "head_dim": 128}
    with pytest.raises(ValueError, match="head_dim"):
        LlamaConfig.check_published(wide)
    with pytest.raises(ValueError, match="sliding_window"):
        LlamaConfig.check_published({"hidden_size": 4096,
                                     "num_attention_heads": 32,
                                     "sliding_window": 128})
