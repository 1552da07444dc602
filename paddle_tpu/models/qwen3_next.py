"""Qwen3-Next family (``model_type`` ``qwen3_next``; Qwen3-Next-80B-A3B) —
a hybrid decoder of linear-attention and gated full-attention layers
over routed experts, built for the serving path.

Layer ``i`` is a FULL layer where ``(i + 1) % full_attention_interval ==
0`` and a LINEAR (Gated DeltaNet) layer otherwise; every layer is ``h +=
mixer(N1(h)); h += moe(N2(h))``. ``N1``, ``N2``, the final norm and the
q/k head norms are zero-centred RMSNorms, ``RMSNorm(x) (1 + w)``.

- linear: ``in_proj_qkvz`` gives, per key head, its q and k
  (``linear_key_head_dim`` each) and its value heads' v and z
  (``linear_value_head_dim`` each); ``in_proj_ba`` per key head its value
  heads' b and a. ``[q; k; v]`` goes through a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps (no bias) and SiLU; q and
  k are L2-normalised a head, q scaled by ``dk^-1/2``, and each key head
  serves its consecutive value heads. ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; per value head, with state ``S
  [dk, dv]``: ``S <- exp(g) S``, ``u = beta (v - S^T k)``, ``S <- S + k
  u^T``, ``o = S^T q`` (``kernels/gated_delta.py``). Then ``o <- w
  RMSNorm(o) silu(z)`` a head and ``out_proj``;
- full: ``q_proj`` gives each head's query and gate (``head_dim`` each);
  q and k head norms; rotary (``rope_theta``) on the first
  ``head_dim x partial_rotary_factor`` dims of each head; ``softmax(q k^T
  / sqrt(head_dim)) v`` with ``heads / kv_heads`` q heads a kv head;
  times ``sigmoid(gate)``; ``o_proj``;
- moe (every layer: ``decoder_sparse_step`` 1, ``mlp_only_layers``
  empty): a router over all ``num_experts`` in float32, softmax, top
  ``num_experts_per_tok``, renormalised over the chosen
  (``norm_topk_prob``); SwiGLU experts of ``moe_intermediate_size``; a
  shared SwiGLU of ``shared_expert_intermediate_size`` times
  ``sigmoid(shared_expert_gate(x))``, a scalar a token.

**A chip's share**, as ``models/exaone_moe.py`` states it: ``num_experts``
experts held ``[expert_offset, expert_offset + num_experts)`` of the
``router_width`` the router scores, and ``vocab_size`` rows of the
vocabulary. Weights are drawn in ``dtype`` on the device. The
zero-centred norms hand the serving step ``1 + w``, so its RMSNorm is the
Llama layer's. ``check_published`` refuses a published file whose
settings this model would silently drop.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _rng
from ..core.dtype import to_jax_dtype
from ..nn.layer.layers import Parameter
from .exaone_moe import EMBEDDING_INIT_STD, _Group, _linear, _normal, _swiglu
from .generation import LayerKind

LINEAR, FULL = "linear_attention", "full_attention"
#: std of the zero-centred norms' seeded ``w``: the published initialiser
#: draws 0, at which ``1 + w`` and a plain weight of ones cannot be told
#: apart
NORM_INIT_STD = 0.1
#: ``A`` in ``g = -A softplus(a + dt_bias)`` is drawn log-uniform on
#: ``(A_INIT_MIN, A_INIT_MAX)``: the published initialiser's upper bound,
#: and heads whose state lasts thousands of tokens at the lower (drawn
#: uniform on (0, 16), as the initialiser does, all but a few heads forget
#: within a few tokens, and a state carried wrongly from step to step
#: would hardly show)
A_INIT_MIN, A_INIT_MAX = 1e-3, 16.0


@dataclass
class Qwen3NextConfig:
    vocab_size: int = 151936            # rows of the vocabulary HELD
    hidden_size: int = 2048
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_experts: int = 512              # experts HELD
    #: experts the router scores (the published ``num_experts``); None =
    #: ``num_experts``, the uncut model
    router_width: int | None = None
    #: id, among ``router_width``, of the first expert held
    expert_offset: int = 0
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    #: the routed layers' router (``serving/spec_decode.py::_routed_mlp``)
    router_scoring = "softmax"

    def __post_init__(self):
        if self.router_width is None:
            self.router_width = self.num_experts
        if self.expert_offset < 0 \
                or self.expert_offset + self.num_experts > self.router_width:
            raise ValueError(
                f"Qwen3NextConfig: experts [{self.expert_offset}, "
                f"{self.expert_offset + self.num_experts}) are not among "
                f"the router's {self.router_width}")
        if self.num_experts_per_tok > self.router_width:
            raise ValueError(f"Qwen3NextConfig: {self.num_experts_per_tok} "
                             f"experts a token of {self.router_width}")
        for a, b, what in (
                (self.num_attention_heads, self.num_key_value_heads,
                 "q heads over kv heads"),
                (self.linear_num_value_heads, self.linear_num_key_heads,
                 "linear value heads over key heads")):
            if a % b:
                raise ValueError(f"Qwen3NextConfig: {a} {what} do not "
                                 f"group ({b})")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"Qwen3NextConfig: rotary on {self.rotary_dim} "
                             f"of {self.head_dim} dims")

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def layer_types(self):
        return [FULL if (i + 1) % self.full_attention_interval == 0
                else LINEAR for i in range(self.num_hidden_layers)]

    def layer_kinds(self):
        """``models.generation.LayerKind`` of each layer run."""
        return tuple(
            LayerKind(linear=True, rope=False, mlp="sparse") if t == LINEAR
            else LayerKind(rope=True, rotary_dim=self.rotary_dim,
                           qk_norm=True, gate=True, mlp="sparse")
            for t in self.layer_types)

    @classmethod
    def check_published(cls, cfg):
        """Refuse, each by name, what this model would silently drop of
        a published file ``cfg`` (the whole file as a dict)."""
        dropped = []
        n = cfg["num_hidden_layers"]
        if cfg.get("use_sliding_window"):
            dropped.append("use_sliding_window is true; every full layer "
                           "here attends to every key")
        if cfg.get("rope_scaling") is not None:
            dropped.append("rope_scaling is set; this path rotates by "
                           "rope_theta alone")
        if cfg.get("decoder_sparse_step", 1) != 1:
            dropped.append(f"decoder_sparse_step is "
                           f"{cfg['decoder_sparse_step']}; every layer here "
                           f"is routed")
        if cfg.get("mlp_only_layers"):
            dropped.append("mlp_only_layers is not empty; every layer here "
                           "is routed")
        mlp = cfg.get("mlp_layer_types")
        if mlp is not None and (len(mlp) < n or set(mlp) != {"sparse"}):
            dropped.append("mlp_layer_types disagrees with "
                           "decoder_sparse_step 1 and no mlp_only_layers")
        interval = cfg.get("full_attention_interval", 4)
        if not isinstance(interval, int) or interval < 1 or interval > n:
            dropped.append(f"full_attention_interval {interval!r}: this "
                           f"path runs a full layer every interval-th layer "
                           f"within the layers run")
        types = cfg.get("layer_types")
        if types is not None and list(types[:n]) != [
                FULL if (i + 1) % interval == 0 else LINEAR
                for i in range(n)]:
            dropped.append("layer_types disagrees with "
                           "full_attention_interval")
        if cfg.get("linear_num_value_heads", 32) \
                % cfg.get("linear_num_key_heads", 16):
            dropped.append("linear_num_value_heads is not a multiple of "
                           "linear_num_key_heads")
        if cfg.get("linear_conv_kernel_dim", 4) < 2:
            dropped.append("linear_conv_kernel_dim under 2: the slot keeps "
                           "the inputs before a step")
        for key in ("linear_conv_bias", "linear_use_bias"):
            if cfg.get(key):
                dropped.append(f"{key} is true; this path's linear layers "
                               f"have no bias")
        for key in ("attention_bias", "mlp_bias"):
            if cfg.get(key):
                dropped.append(f"{key} is true; this path's projections "
                               f"have no bias")
        if cfg.get("hidden_act", "silu") != "silu":
            dropped.append(f"hidden_act {cfg['hidden_act']!r} is not "
                           f"SwiGLU's")
        if cfg.get("tie_word_embeddings"):
            dropped.append("tie_word_embeddings is true; this model has "
                           "its own head")
        if cfg.get("num_nextn_predict_layers", 0):
            dropped.append("num_nextn_predict_layers is set; this path "
                           "runs no multi-token-prediction block")
        if dropped:
            raise ValueError("Qwen3NextConfig would drop: "
                             + "; ".join(dropped))


# ---------------------------------------------------------------------------
# weights: groups of parameters by their published names
# ---------------------------------------------------------------------------

def _norm(n, dtype):
    """A zero-centred RMSNorm's ``w`` (the layer scales by ``1 + w``)."""
    return _Group(weight=_normal((n,), NORM_INIT_STD, dtype))


def _linear_attn(cfg, dtype):
    h, std = cfg.hidden_size, cfg.initializer_range
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    channels = 2 * hk * dk + hv * dv
    taps = cfg.linear_conv_kernel_dim
    a_log = jax.random.uniform(_rng.next_key(), (hv,), jnp.float32,
                               minval=jnp.log(A_INIT_MIN),
                               maxval=jnp.log(A_INIT_MAX))
    return _Group(
        in_proj_qkvz=_linear(h, 2 * hk * dk + 2 * hv * dv, std, dtype),
        in_proj_ba=_linear(h, 2 * hv, std, dtype),
        # torch's Conv1d initialiser: uniform within 1 / sqrt(fan_in)
        conv1d=_Group(weight=Parameter(jax.random.uniform(
            _rng.next_key(), (channels, 1, taps), jnp.float32,
            minval=-taps ** -0.5, maxval=taps ** -0.5).astype(dtype))),
        dt_bias=Parameter(jnp.ones((hv,), dtype)),
        A_log=Parameter(a_log.astype(dtype)),
        norm=_Group(weight=Parameter(jnp.ones((dv,), dtype))),
        out_proj=_linear(hv * dv, h, std, dtype))


def _full_attn(cfg, dtype):
    h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    return _Group(q_proj=_linear(h, 2 * H * d, std, dtype),
                  k_proj=_linear(h, Hkv * d, std, dtype),
                  v_proj=_linear(h, Hkv * d, std, dtype),
                  o_proj=_linear(H * d, h, std, dtype),
                  q_norm=_norm(d, dtype), k_norm=_norm(d, dtype))


def _moe(cfg, dtype):
    h, m, std = cfg.hidden_size, cfg.moe_intermediate_size, \
        cfg.initializer_range
    e = cfg.num_experts
    return _Group(
        gate=_linear(h, cfg.router_width, std, dtype),
        experts=_Group(gate_proj=_normal((e, h, m), std, dtype),
                       up_proj=_normal((e, h, m), std, dtype),
                       down_proj=_normal((e, m, h), std, dtype)),
        shared_expert=_swiglu(h, cfg.shared_expert_intermediate_size, std,
                              dtype),
        shared_expert_gate=_linear(h, 1, std, dtype))


def _layer(cfg, kind, dtype):
    h = cfg.hidden_size
    mixer = {"linear_attn": _linear_attn(cfg, dtype)} if kind == LINEAR \
        else {"self_attn": _full_attn(cfg, dtype)}
    return _Group(input_layernorm=_norm(h, dtype),
                  post_attention_layernorm=_norm(h, dtype),
                  mlp=_moe(cfg, dtype), **mixer)


def _centred(w):
    """``1 + w`` in float32, stored back in ``w``'s dtype."""
    return (1.0 + w._data.astype(jnp.float32)).astype(w._data.dtype)


class Qwen3NextForCausalLM(nn.Layer):
    """The weights of a Qwen3-Next decoder (or of one chip's share of
    it), under their published names, and the pytree the serving step
    runs over. ``LLMEngine(model)`` is the one way to run it: there is
    no eager ``forward`` (the plain forward that checks the engine is the
    benchmark's reference, ``benchmark/references/qwen3_next.py``)."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        dtype = to_jax_dtype(config.dtype)
        self._dtype = config.dtype
        h, std = config.hidden_size, config.initializer_range
        self.model = _Group(
            embed_tokens=_Group(weight=_normal(
                (config.vocab_size, h), EMBEDDING_INIT_STD, dtype)),
            layers=nn.LayerList([_layer(config, t, dtype)
                                 for t in config.layer_types]),
            norm=_norm(h, dtype))
        self.lm_head = _linear(h, config.vocab_size, std, dtype)

    def serving_params(self):
        """The per-layer pytree ``models.generation.extract_params``
        hands the serving step; a layer's keys follow its kind. The
        zero-centred norms go as ``1 + w``; the convolution's weight as
        ``[taps, channels]``."""
        layers = []
        for lyr, kind in zip(self.model.layers, self.config.layer_kinds()):
            m = lyr.mlp
            p = {"ln1": _centred(lyr.input_layernorm.weight),
                 "ln2": _centred(lyr.post_attention_layernorm.weight),
                 "router": m.gate.weight._data,
                 "experts_gate": m.experts.gate_proj._data,
                 "experts_up": m.experts.up_proj._data,
                 "experts_down": m.experts.down_proj._data,
                 "shared_gate": m.shared_expert.gate_proj.weight._data,
                 "shared_up": m.shared_expert.up_proj.weight._data,
                 "shared_down": m.shared_expert.down_proj.weight._data,
                 "shared_expert_gate": m.shared_expert_gate.weight._data}
            if kind.linear:
                a = lyr.linear_attn
                p.update(in_qkvz=a.in_proj_qkvz.weight._data,
                         in_ba=a.in_proj_ba.weight._data,
                         conv=jnp.transpose(a.conv1d.weight._data[:, 0]),
                         A_log=a.A_log._data, dt_bias=a.dt_bias._data,
                         out_norm=a.norm.weight._data,
                         out_proj=a.out_proj.weight._data)
            else:
                a = lyr.self_attn
                p.update(q=a.q_proj.weight._data, k=a.k_proj.weight._data,
                         v=a.v_proj.weight._data, o=a.o_proj.weight._data,
                         q_norm=_centred(a.q_norm.weight),
                         k_norm=_centred(a.k_norm.weight))
            layers.append(p)
        return {"embed": self.model.embed_tokens.weight._data,
                "norm": _centred(self.model.norm.weight),
                "lm_head": self.lm_head.weight._data, "layers": layers}


__all__ = ["FULL", "LINEAR", "Qwen3NextConfig", "Qwen3NextForCausalLM"]
