"""paddle_tpu.models — reference model families (the capability surface of
python/paddle/vision/models plus the LLM configs the reference targets with
its fleet/auto-parallel stacks; see BASELINE.md stepping-stone configs).

All models are plain ``paddle_tpu.nn`` Layers: they run eagerly, compile under
``paddle_tpu.jit``, and shard under ``paddle_tpu.distributed``.
"""
from .lenet import LeNet  # noqa: F401
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152  # noqa: F401
from .bert import BertConfig, BertModel, BertForPretraining  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig, LlamaModel, LlamaForCausalLM,
    llama2_7b_config, llama2_13b_config, llama_tiny_config,
)
from .unet import UNetModel, sd_unet, sd_unet_tiny  # noqa: F401
from .generation import Generator, generate  # noqa: F401
from .llama_moe import (  # noqa: F401
    LlamaMoeConfig, LlamaMoeModel, LlamaMoeForCausalLM,
    llama_moe_tiny_config,
)
from .exaone_moe import ExaoneMoeConfig, ExaoneMoeForCausalLM  # noqa: F401
from .hf_interop import (  # noqa: F401
    llama_from_hf, load_llama_state_dict, llama_config_from_hf,
    bert_from_hf, load_bert_state_dict, bert_config_from_hf,
)
