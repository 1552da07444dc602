"""Mixtral-style MoE Llama — a TRAINING model whose gate drops tokens.

What this is: a GShard gate with ``capacity_factor`` 1.25. Each expert
gets 1.25 x its fair share of slots a batch, and a token whose expert is
full is DROPPED (it passes through the residual alone); the gate's
load-balancing aux loss is kept as layer state and added to the training
loss. That is a training recipe. It is not what the serving path routes
with: ``serving/engine.py`` has no body for this class's layers, and a
serving step must not drop a token. The dropless, sigmoid-routed expert
layer that is told which experts it holds is ``nn/moe_dropless.py`` (over
``kernels/grouped_matmul.py``), run by ``models/exaone_moe.py``.

The dense decoder's SwiGLU MLP is replaced (every
``moe_layer_interval``-th layer) by a GShard-gated mixture of SwiGLU
experts through :class:`~paddle_tpu.incubate.distributed.models.moe
.MoELayer` — the same MoE formulation the reference ships
(reference: python/paddle/incubate/distributed/models/moe/moe_layer.py
:261; gshard gate gate/gshard_gate.py). The gate's load-balancing aux
loss accumulates across layers into the training loss, and at training
scale the stacked expert weights shard over the ``ep`` mesh axis
(distributed/expert_parallel.moe_alltoall is the explicit-schedule
form; __graft_entry__ dryrun stage [4] proves the wire pattern).
"""
from __future__ import annotations

from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from .llama import (
    LlamaAttention, LlamaConfig, LlamaMLP, LlamaRMSNorm,
)


@dataclass
class LlamaMoeConfig(LlamaConfig):
    num_experts: int = 8
    moe_top_k: int = 2
    capacity_factor: float = 1.25
    moe_layer_interval: int = 1     # 1 = every layer is MoE (Mixtral)
    aux_loss_weight: float = 0.01

    #: this class has not written down what its model would drop of a
    #: published MoE file (its expert keys are its own, not a published
    #: config's): it carries no check yet, so a harness that asks finds
    #: the rules of its nearest base that has them (LlamaConfig's)
    check_published = None


class LlamaMoeDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaMoeConfig, use_moe: bool):
        super().__init__()
        from ..incubate.distributed.models.moe import MoELayer
        self.input_layernorm = LlamaRMSNorm(config.hidden_size,
                                            config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size,
                                                     config.rms_norm_eps)
        if use_moe:
            experts = [LlamaMLP(config) for _ in range(config.num_experts)]
            self.mlp = MoELayer(config.hidden_size, experts, gate="gshard",
                                top_k=config.moe_top_k,
                                capacity_factor=config.capacity_factor)
        else:
            self.mlp = LlamaMLP(config)

    @property
    def aux_loss(self):
        return getattr(self.mlp, "aux_loss", None)

    def forward(self, hidden_states, position_ids=None, attn_mask=None,
                rope_cs=None):
        h = hidden_states + self.self_attn(
            self.input_layernorm(hidden_states), position_ids, attn_mask,
            rope_cs)
        return h + self.mlp(self.post_attention_layernorm(h))


class LlamaMoeModel(nn.Layer):
    def __init__(self, config: LlamaMoeConfig):
        super().__init__()
        from ..core.flags import GLOBAL_FLAGS
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        layers = [
            LlamaMoeDecoderLayer(
                config, use_moe=(i % config.moe_layer_interval == 0))
            for i in range(config.num_hidden_layers)]
        if GLOBAL_FLAGS.get("scan_layers"):
            # scan the DENSE runs between routed layers: MoE layers
            # mutate gate aux-loss state each forward and must stay
            # unrolled; consecutive dense layers collapse into one
            # lax.scan (nn/scan_stack.py). State names keep the global
            # layer indices, so checkpoints match the unrolled layout.
            from ..nn.scan_stack import stack_homogeneous_runs
            self.layers = stack_homogeneous_runs(
                layers, scannable=lambda l: isinstance(l.mlp, LlamaMLP))
        else:
            self.layers = nn.LayerList(layers)
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None):
        from ..nn.scan_stack import LayerStack, effective_remat_policy
        h = self.embed_tokens(input_ids)
        pos = position_ids if position_ids is not None \
            else input_ids.shape[1]
        rope_cs = F.rope_tables(pos, self.config.head_dim,
                                self.config.rope_theta)
        policy = effective_remat_policy(self.config.remat)
        for layer in self.layers:
            if isinstance(layer, LayerStack):
                h = layer(h, position_ids, attn_mask, rope_cs,
                          remat_policy=policy)
            elif policy != "none":
                from ..distributed.fleet.recompute import recompute
                h = recompute(layer, h, position_ids, attn_mask, rope_cs)
            else:
                h = layer(h, position_ids, attn_mask, rope_cs)
        return self.norm(h)

    def aux_loss(self):
        """Sum of per-layer gate load-balancing losses (this forward)."""
        total = None
        for layer in self.layers:
            al = getattr(layer, "aux_loss", None)
            if al is None:
                continue
            total = al if total is None else total + al
        return total


class LlamaMoeForCausalLM(nn.Layer):
    """Causal LM over the MoE decoder; ``forward(ids, labels=ids)``
    returns (logits|None, loss) with the gate aux loss folded in at
    ``aux_loss_weight`` (the reference accumulates it the same way)."""

    def __init__(self, config: LlamaMoeConfig):
        super().__init__()
        self.config = config
        self.model = LlamaMoeModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
        from .llama import init_llama_weights
        init_llama_weights(self, config.initializer_range)

    def forward(self, input_ids, labels=None, position_ids=None,
                attn_mask=None):
        from .. import tensor as T
        h = self.model(input_ids, position_ids, attn_mask)
        if labels is not None and self.config.loss_chunk_size:
            # memory-efficient chunked linear+CE head (dense-family
            # parity — no full [tokens, vocab] logits on this path)
            w = (self.model.embed_tokens.weight if self.lm_head is None
                 else self.lm_head.weight)
            loss = F.fused_linear_cross_entropy(
                h[:, :-1], w, labels[:, 1:],
                chunk_size=self.config.loss_chunk_size,
                transpose_weight=self.lm_head is None)
            aux = self.model.aux_loss()
            if aux is not None:
                loss = loss + self.config.aux_loss_weight * aux
            return None, loss
        if self.lm_head is None:
            logits = T.matmul(h, self.model.embed_tokens.weight,
                              transpose_y=True)
        else:
            logits = self.lm_head(h)
        if labels is None:
            return logits
        loss = F.cross_entropy(
            logits[:, :-1].reshape([-1, self.config.vocab_size]),
            labels[:, 1:].reshape([-1]), reduction="mean")
        aux = self.model.aux_loss()
        if aux is not None:
            loss = loss + self.config.aux_loss_weight * aux
        return logits, loss

    def flops_per_token(self, seq_len, remat_policy=None):
        """Active-parameter FLOPs/token: attention + top_k of the expert
        FFNs (the MoE MFU convention) + embeddings/head. Dense runs
        scanned into a LayerStack contribute every stacked parameter
        (all dense params are active). ``remat_policy='full'`` adds the
        recomputed forward like the dense family."""
        from ..nn.scan_stack import LayerStack, effective_remat_policy
        c = self.config
        active = 0
        for layer in self.model.layers:
            if isinstance(layer, LayerStack):
                active += sum(p.size for p in layer.parameters())
                continue
            for p in layer.self_attn.parameters():
                active += p.size
            mlp = layer.mlp
            if hasattr(mlp, "experts"):
                per_expert = sum(p.size for p in mlp.experts[0].parameters())
                active += c.moe_top_k * per_expert
                active += c.hidden_size * c.num_experts   # gate
            else:
                active += sum(p.size for p in mlp.parameters())
        active += self.model.embed_tokens.weight.size
        if self.lm_head is not None:
            active += self.lm_head.weight.size
        attn = 12 * c.num_hidden_layers * c.hidden_size * seq_len
        total = 6 * active + attn
        policy = remat_policy if remat_policy is not None \
            else effective_remat_policy(c.remat)
        if policy == "full":
            total += 2 * active + attn // 3
        return total


def llama_moe_tiny_config(**overrides):
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=128,
                num_experts=4, moe_top_k=2)
    base.update(overrides)
    return LlamaMoeConfig(**base)


__all__ = ["LlamaMoeConfig", "LlamaMoeModel", "LlamaMoeForCausalLM",
           "llama_moe_tiny_config"]
