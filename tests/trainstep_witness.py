"""The compiled train step read as text (ISSUE 40): a small step built the
way the training cells build theirs, and what its optimised HLO holds under
``phase.optimizer``. Shared by ``tests/test_trainstep_per_leaf.py`` (the CPU
and the CPU mesh) and ``tests/test_tpu_compile.py`` (the described v5e).
"""
import math
import re

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.jit.hlo_forensics import instruction_metadata
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.profiler import phases

#: SmolLM2-1.7B's shapes (benchmark/configs/smollm2-1.7b.json) at a quarter
#: of its widths and two of its layers: heads of 64, a feed-forward four
#: times the hidden size, a tied embedding, the chunked loss and the
#: replay. A test holds 10 M parameters and their moments, not 235 M.
SMOLLM2_QUARTER = dict(
    hidden_size=512, intermediate_size=2048, num_attention_heads=8,
    num_key_value_heads=8, num_hidden_layers=2, vocab_size=1024,
    max_position_embeddings=256, rope_theta=130000.0,
    tie_word_embeddings=True, loss_chunk_size=64, remat=True)


def smollm2_like_step(sharding=None, batch=4, seq=128, **cfg):
    """``(step, model, opt, ids)``: AdamW, bf16 autocast, one seeded
    batch, as ``benchmark/runners/train_steps.py`` has them."""
    paddle.seed(11)
    model = LlamaForCausalLM(llama_tiny_config(**{**SMOLLM2_QUARTER, **cfg}))
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(ids):
        with paddle.amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            return model(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt, sharding=sharding)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, model.config.vocab_size, (batch, seq)), dtype="int64")
    return step, model, opt, ids


def optimizer_instructions(text):
    """``(name, opcode, line)`` of every instruction, inside fusions too,
    that the program's phase table (``phases.parse``: its own metadata,
    else its first reader's) charges to ``optimizer``."""
    lines = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ", line)
        if m:
            lines[m.group(1)] = line
    table = phases.parse(text)[0]
    return [(name, opcode, lines[name])
            for name, opcode, _, _ in instruction_metadata(text)
            if table[name][0] == "optimizer"]


def result_elems(line):
    """The largest array in an instruction's result type."""
    head = line.split(" = ", 1)[1]
    head = re.split(r" [a-z][\w\-]*\(", head, maxsplit=1)[0]
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\w+\[([\d,]*)\]", head)),
               default=0)


def flat_bucket_traces(text, bucket_elems):
    """What a flat bucket would leave under ``phase.optimizer``: a
    concatenation, a ``dynamic-update-slice``, the Pallas bucket kernel,
    or any result at least as large as all the leaves together."""
    found = []
    for name, opcode, line in optimizer_instructions(text):
        if opcode in ("concatenate", "dynamic-update-slice") \
                or (opcode == "custom-call" and "fused_adamw" in line) \
                or result_elems(line) >= bucket_elems:
            found.append(f"{opcode} {line.strip()[:140]}")
    return found


def collectives_under_optimizer(text):
    return [f"{opcode} {name}"
            for name, opcode, _ in optimizer_instructions(text)
            if opcode.removesuffix("-start") in (
                "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")]
