"""Llama-2 family — the flagship model (stepping-stone config 3 of
SURVEY.md section 6, the north-star TP×PP×Sharding workload).

Reference analogs: the reference has no in-tree Llama, but its fleet stack is
built for exactly this architecture (fused_rope paddle/phi/kernels/fusion/gpu/
fused_rope_kernel.cu, fused_rms_norm, swiglu python/paddle/incubate/nn/
functional/, flash_attn paddle/phi/kernels/gpu/flash_attn_kernel.cu). Here the
architecture is expressed TPU-first: einsum/matmul shapes that tile onto the
MXU, bf16-friendly, RoPE/RMSNorm/SwiGLU as fusable jnp compositions that the
Pallas kernel tier can override (paddle_tpu/ops/).

Weight layout notes (for tensor parallelism): q/k/v/gate/up projections are
column-sharded, o/down row-sharded — see paddle_tpu/distributed/parallelize.py.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from .. import nn
from ..nn import functional as F
from .. import tensor as T
from ..profiler import phases


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # std of the N(0, std) weight init applied to every Linear/Embedding
    # (reference: PaddleNLP LlamaConfig.initializer_range; keeps
    # tied-embedding logits O(1) at init so the initial loss sits at
    # ln(vocab))
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    # >0: train-time loss uses the chunked fused matmul+CE head (full
    # [tokens, vocab] logits never materialized; forward returns (None, loss))
    loss_chunk_size: int = 0
    # recompute each decoder layer's activations in backward (the 1B+
    # single-chip memory recipe: trade ~1/3 more FLOPs for O(layers) fewer
    # live activations). Superseded by FLAGS_remat_policy (none /
    # dots_saveable / full); kept as the legacy spelling of "full".
    remat: bool = False

    def __post_init__(self):
        if self.num_attention_heads <= 0 or \
                self.hidden_size % self.num_attention_heads != 0:
            raise ValueError(
                f"LlamaConfig: hidden_size ({self.hidden_size}) must be "
                f"divisible by num_attention_heads "
                f"({self.num_attention_heads}) — head_dim would be "
                f"fractional and the attention reshape would fail deep "
                f"inside the first forward")
        if self.num_key_value_heads <= 0 or \
                self.num_attention_heads % self.num_key_value_heads != 0:
            raise ValueError(
                f"LlamaConfig: num_attention_heads "
                f"({self.num_attention_heads}) must be divisible by "
                f"num_key_value_heads ({self.num_key_value_heads}) for "
                f"GQA head pairing")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @classmethod
    def check_published(cls, cfg):
        """Refuse, each by name, what this class's model would silently
        drop of a published ``config.json`` (``cfg``: the whole file as
        a dict): full causal attention with heads of ``hidden_size /
        num_attention_heads``, plain RoPE, SwiGLU, no bias anywhere. A
        wrong model under a real name is worse than none."""
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


def llama2_7b_config():
    return LlamaConfig()


def llama2_13b_config():
    return LlamaConfig(hidden_size=5120, intermediate_size=13824,
                       num_hidden_layers=40, num_attention_heads=40,
                       num_key_value_heads=40)


def llama_tiny_config(**kw):
    """Tiny config for tests / dryruns (shapes still MXU-aligned)."""
    base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, max_position_embeddings=256)
    base.update(kw)
    return LlamaConfig(**base)


def apply_rotary_pos_emb(q, k, position_ids=None, theta=10000.0, rope_cs=None):
    """RoPE over paddle-layout [b, s, h, d] q/k.

    TPU-native analog of fused_rotary_position_embedding (reference:
    paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu); the composition is
    left to XLA fusion, and the Pallas tier can override op 'rope'.
    ``rope_cs``: optional precomputed (cos, sin) tables shared across layers.
    """
    if rope_cs is not None:
        return F.rope(q, k, cos=rope_cs[0], sin=rope_cs[1], theta=theta)
    return F.rope(q, k, position_ids=position_ids, theta=theta)


LlamaRMSNorm = nn.RMSNorm


def init_llama_weights(root_layer, std):
    """Llama init recipe: every Linear / Embedding weight ~ N(0, std)
    (norm scales stay at ones). The layer defaults (Xavier / N(0,1)) are
    fine standalone but wrong jointly: a N(0,1) embedding through a tied
    head produces O(sqrt(hidden)) logits at init. Shared by the dense
    and MoE causal-LM families. Scanned stacks (nn.LayerStack) hold the
    per-layer Linears only as an unregistered template, so the recipe
    re-draws their leading-axis-stacked weights keyed off the template
    owner's type."""
    from ..nn.initializer import Normal
    from ..nn.scan_stack import LayerStack

    init = Normal(0.0, std)
    for layer in root_layer.sublayers(include_self=True):
        w = getattr(layer, "weight", None)
        if isinstance(layer, (nn.Linear, nn.Embedding)) and w is not None:
            w._inplace_update(init(w.shape, w._data.dtype))
        if isinstance(layer, LayerStack):
            for _, p, owner, leaf in layer.stacked_entries():
                if isinstance(owner, (nn.Linear, nn.Embedding)) \
                        and leaf == "weight":
                    p._inplace_update(init(p.shape, p._data.dtype))


class LlamaAttention(nn.Layer):
    """GQA attention with RoPE; [b, s, h, d] layout end to end."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, hd = config.hidden_size, config.head_dim
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.q_proj = nn.Linear(h, self.num_heads * hd, bias_attr=False)
        self.k_proj = nn.Linear(h, self.num_kv_heads * hd, bias_attr=False)
        self.v_proj = nn.Linear(h, self.num_kv_heads * hd, bias_attr=False)
        self.o_proj = nn.Linear(self.num_heads * hd, h, bias_attr=False)

    def forward(self, hidden_states, position_ids=None, attn_mask=None,
                rope_cs=None):
        b, s, _ = hidden_states.shape
        hd = self.config.head_dim
        with phases.phase("attn.qkv"):
            q = self.q_proj(hidden_states).reshape(
                [b, s, self.num_heads, hd])
            k = self.k_proj(hidden_states).reshape(
                [b, s, self.num_kv_heads, hd])
            v = self.v_proj(hidden_states).reshape(
                [b, s, self.num_kv_heads, hd])
            q, k = apply_rotary_pos_emb(q, k, position_ids,
                                        self.config.rope_theta, rope_cs)
        # GQA k/v go to attention with their native head count — both the
        # composed SDPA body and the Pallas flash kernel pair query head j
        # with kv head j // group internally, so the repeated [b, s, hq, d]
        # k/v copies never hit HBM.
        # Causal LM: the causal mask always applies; attn_mask (e.g. padding)
        # is merged on top, never a replacement for it.
        with phases.phase("attn.core"):
            if self.config.use_flash_attention and attn_mask is None:
                out, _ = F.flash_attention(q, k, v, causal=True)
            else:
                out = F.scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask, is_causal=True)
        with phases.phase("attn.out"):
            out = out.reshape([b, s, self.num_heads * hd])
            return self.o_proj(out)


class LlamaMLP(nn.Layer):
    """SwiGLU MLP (reference fused kernel: incubate/nn/functional/swiglu)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, i, bias_attr=False)
        self.up_proj = nn.Linear(h, i, bias_attr=False)
        self.down_proj = nn.Linear(i, h, bias_attr=False)

    @phases.scoped("mlp")
    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, hidden_states, position_ids=None, attn_mask=None,
                rope_cs=None):
        with phases.phase("norm"):
            x = self.input_layernorm(hidden_states)
        x = self.self_attn(x, position_ids, attn_mask, rope_cs)
        with phases.phase("attn.out"):      # the residual add
            h = hidden_states + x
        with phases.phase("norm"):
            x = self.post_attention_layernorm(h)
        with phases.phase("mlp"):
            return h + self.mlp(x)


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        from ..core.flags import GLOBAL_FLAGS
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        layers = [LlamaDecoderLayer(config)
                  for _ in range(config.num_hidden_layers)]
        if GLOBAL_FLAGS.get("scan_layers"):
            # one lax.scan over leading-axis-stacked decoder weights: HLO
            # and trace time O(1) in depth (nn/scan_stack.py); state_dict
            # keeps the per-layer "layers.{i}.*" names either way
            self.layers = nn.LayerStack(layers)
        else:
            self.layers = nn.LayerList(layers)
        self.norm = LlamaRMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None):
        from ..nn.scan_stack import LayerStack, effective_remat_policy
        with phases.phase("embed"):
            h = self.embed_tokens(input_ids)
        # Build the RoPE cos/sin tables once and share across all layers.
        pos = position_ids if position_ids is not None else input_ids.shape[1]
        with phases.phase("attn.qkv"):
            rope_cs = F.rope_tables(pos, self.config.head_dim,
                                    self.config.rope_theta)
        policy = effective_remat_policy(self.config.remat)
        if isinstance(self.layers, LayerStack):
            h = self.layers(h, position_ids, attn_mask, rope_cs,
                            remat_policy=policy)
        elif policy != "none":
            # unrolled path: host-replay recompute (the pre-scan recipe);
            # the tape cannot express dots_saveable, so any non-none
            # policy recomputes the full layer here — use the scanned
            # path for the selective policy.
            from ..distributed.fleet.recompute import recompute
            for layer in self.layers:
                h = recompute(layer, h, position_ids, attn_mask, rope_cs)
        else:
            for layer in self.layers:
                h = layer(h, position_ids, attn_mask, rope_cs)
        with phases.phase("head"):
            return self.norm(h)


class LlamaForCausalLM(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.model = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     bias_attr=False)
        self._init_weights(config.initializer_range)

    def _init_weights(self, std):
        init_llama_weights(self, std)

    def forward(self, input_ids, labels=None, position_ids=None, attn_mask=None):
        h = self.model(input_ids, position_ids, attn_mask)
        if labels is not None and self.config.loss_chunk_size:
            # memory-efficient head: chunked matmul+CE, full logits never
            # materialized (so no logits are returned on this path).
            # Causal shift (next-token objective, the reference/HF
            # convention — position i predicts labels[i+1]): without it a
            # tied-embedding model trivially "predicts" its own input via
            # the residual stream and the loss collapses to ~0.
            w = (self.model.embed_tokens.weight if self.lm_head is None
                 else self.lm_head.weight)
            with phases.phase("loss"):
                loss = F.fused_linear_cross_entropy(
                    h[:, :-1].reshape([-1, self.config.hidden_size]), w,
                    labels[:, 1:].reshape([-1]),
                    chunk_size=self.config.loss_chunk_size,
                    transpose_weight=self.lm_head is None)
            return None, loss
        with phases.phase("head"):
            if self.lm_head is None:
                logits = T.matmul(h, self.model.embed_tokens.weight,
                                  transpose_y=True)
            else:
                logits = self.lm_head(h)
        if labels is None:
            return logits
        # same causal shift as the chunked path
        with phases.phase("loss"):
            loss = F.cross_entropy(
                logits[:, :-1].reshape([-1, self.config.vocab_size]),
                labels[:, 1:].reshape([-1]), reduction="mean")
        return logits, loss

    def flops_per_token(self, seq_len, remat_policy=None):
        """Approximate training FLOPs/token (6N + attention), for MFU.

        Under ``remat_policy='full'`` (or the legacy ``config.remat``)
        the backward pass re-runs the decoder forward, so the hardware
        executes one extra forward per token: +2N params FLOPs and +1/3
        of the attention term (fwd is 4 of the 12·L·h·s total). MFU
        reported against this number counts the FLOPs actually executed
        instead of silently inflating tokens/s-per-FLOP.
        ``dots_saveable`` only recomputes the cheap elementwise tail
        (matmul outputs are saved), which this counting ignores."""
        from ..nn.scan_stack import effective_remat_policy
        c = self.config
        n_params = sum(p.size for p in self.parameters())
        attn = 12 * c.num_hidden_layers * c.hidden_size * seq_len
        total = 6 * n_params + attn
        policy = remat_policy if remat_policy is not None \
            else effective_remat_policy(c.remat)
        if policy == "full":
            total += 2 * n_params + attn // 3
        return total
