"""EXAONE-MoE family (``model_type`` ``exaone_moe``; K-EXAONE-236B-A23B) —
a decoder whose layers differ in kind, built for the serving path.

The layer, from the published ``config.json``:

- attention: pre-norm; q/k/v projections with ``head_dim`` a field (64
  heads of 128 on hidden 6144: q is wider than hidden); RMSNorm over the
  ``head_dim`` of every q and k head; ``layer_types[i]`` says whether
  layer ``i`` is a ``sliding_attention`` layer (a query sees its last
  ``sliding_window`` keys, rotary embedding on q and k) or a
  ``full_attention`` one (every key, no rotary);
- feed-forward: ``mlp_layer_types[i]`` ``dense`` (SwiGLU of
  ``intermediate_size``) or ``sparse``: a sigmoid router over all experts
  in float32, ``num_experts_per_tok`` chosen by score + correction bias,
  gates normalised over the chosen and scaled by
  ``routed_scaling_factor``, experts SwiGLU of ``moe_intermediate_size``,
  plus one shared expert every token takes (``nn/moe_dropless.py``).

**A chip's share.** The model is told what it holds: ``num_experts``
experts ``[expert_offset, expert_offset + num_experts)`` of the
``router_width`` the router scores (expert parallel: the other chips'
experts, and the exchange that sums the parts, are not stood in for), and
``vocab_size`` rows of the vocabulary (ids local to them). Uncut,
``router_width`` is ``num_experts``.

Weights are drawn in ``dtype`` on the device, one array at a time: no
float32 copy of the model exists at any time (``init_llama_weights`` draws
f32 and casts, 4 B a parameter at the peak). ``check_published`` refuses a
published file whose settings this model would silently drop.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import nn
from ..core import random as _rng
from ..core.dtype import to_jax_dtype
from ..nn.layer.layers import Parameter
from .generation import LayerKind

SLIDING, FULL = "sliding_attention", "full_attention"
#: std of the seeded embedding rows. Not ``initializer_range``: a layer's
#: input is RMS-normalised, so a matrix's scale is free, but the residual
#: stream's is not. At 0.02 a token's own row is a twentieth of the first
#: attention output (a mean over its context), every token of a chunk then
#: looks like its neighbours and routes to the same few experts (picks a
#: held expert over 384 tokens: 2 to 68, and which experts stay cold
#: depends on the seed); at 1 the token's identity dominates its hidden
#: state, as a trained model's does, and the seeded router loads its
#: experts evenly (11 to 38), as the published one is trained to
EMBEDDING_INIT_STD = 1.0
_PATTERN = {"L": SLIDING, "G": FULL}


def _pattern_types(pattern, n):
    return [_PATTERN[pattern[i % len(pattern)]] for i in range(n)]


@dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600            # rows of the vocabulary HELD
    hidden_size: int = 6144
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_parameters: dict = field(
        default_factory=lambda: {"rope_theta": 1000000,
                                 "rope_type": "default"})
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    #: per layer, at least ``num_hidden_layers`` long (a cut depth reads
    #: the published list's head); None = from the pattern
    layer_types: list | None = None
    #: per layer likewise; None = ``first_k_dense_replace`` dense, then
    #: sparse
    mlp_layer_types: list | None = None
    first_k_dense_replace: int = 1
    num_experts: int = 128              # experts HELD
    #: experts the router scores (the published ``num_experts``); None =
    #: ``num_experts``, the uncut model
    router_width: int | None = None
    #: id, among ``router_width``, of the first expert held
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = _pattern_types(self.sliding_window_pattern, n)
        if self.mlp_layer_types is None:
            k = self.first_k_dense_replace
            self.mlp_layer_types = ["dense"] * k + ["sparse"] * (n - k)
        for name in ("layer_types", "mlp_layer_types"):
            if len(getattr(self, name)) < n:
                raise ValueError(
                    f"ExaoneMoeConfig: {name} names "
                    f"{len(getattr(self, name))} layers, "
                    f"num_hidden_layers is {n}")
        if self.router_width is None:
            self.router_width = self.num_experts
        if self.expert_offset < 0 \
                or self.expert_offset + self.num_experts > self.router_width:
            raise ValueError(
                f"ExaoneMoeConfig: experts [{self.expert_offset}, "
                f"{self.expert_offset + self.num_experts}) are not among "
                f"the router's {self.router_width}")
        if self.num_experts_per_tok > self.router_width:
            raise ValueError(
                f"ExaoneMoeConfig: {self.num_experts_per_tok} experts a "
                f"token of {self.router_width}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"ExaoneMoeConfig: {self.num_attention_heads} q heads do "
                f"not group over {self.num_key_value_heads} kv heads")

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])

    def layer_kinds(self):
        """``models.generation.LayerKind`` of each layer run."""
        return tuple(
            LayerKind(window=self.sliding_window if t == SLIDING else None,
                      rope=t == SLIDING, qk_norm=True, mlp=m)
            for t, m in zip(self.layer_types[:self.num_hidden_layers],
                            self.mlp_layer_types))

    @classmethod
    def check_published(cls, cfg):
        """Refuse, each by name, what this model would silently drop of
        a published file ``cfg`` (the whole file as a dict)."""
        dropped = []
        n = cfg["num_hidden_layers"]
        if cfg.get("rope_scaling") is not None:
            dropped.append("rope_scaling is set; this path rotates by "
                           "rope_theta alone")
        rp = cfg.get("rope_parameters") or {}
        if rp.get("rope_type", "default") != "default":
            dropped.append(f"rope_parameters.rope_type "
                           f"{rp['rope_type']!r} is not 'default'")
        if "rope_theta" not in rp:
            dropped.append("rope_parameters carries no rope_theta")
        for key in ("attention_bias", "mlp_bias"):
            if cfg.get(key):
                dropped.append(f"{key} is true; this path's projections "
                               f"have no bias")
        if cfg.get("hidden_act", "silu") != "silu":
            dropped.append(f"hidden_act {cfg['hidden_act']!r} is not "
                           f"SwiGLU's")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid":
            dropped.append(f"scoring_func {cfg['scoring_func']!r}; this "
                           f"router scores with a sigmoid")
        for key in ("n_group", "topk_group"):
            if cfg.get(key, 1) != 1:
                dropped.append(f"{key} is {cfg[key]}; this router picks "
                               f"over all experts, no group limit")
        if cfg.get("num_shared_experts", 1) != 1:
            dropped.append(f"num_shared_experts is "
                           f"{cfg['num_shared_experts']}; this layer has "
                           f"one shared expert")
        if cfg.get("num_nextn_predict_layers", 0):
            dropped.append("num_nextn_predict_layers is set; this path "
                           "runs no multi-token-prediction block")
        if cfg.get("tie_word_embeddings"):
            dropped.append("tie_word_embeddings is true; this model has "
                           "its own head")
        types = cfg.get("layer_types")
        pattern = cfg.get("sliding_window_pattern")
        if types is not None:
            bad = sorted(set(types) - {SLIDING, FULL})
            if bad:
                dropped.append(f"layer_types names {bad}, which this path "
                               f"has no layer for")
            elif pattern and list(types) != _pattern_types(pattern,
                                                           len(types)):
                dropped.append(f"layer_types disagrees with "
                               f"sliding_window_pattern {pattern!r}")
            if len(types) < n:
                dropped.append(f"layer_types names {len(types)} layers, "
                               f"num_hidden_layers is {n}")
            windows = cfg.get("sliding_windows")
            w = cfg.get("sliding_window")
            if windows is not None and list(windows) != [
                    w if t == SLIDING else 0 for t in types]:
                dropped.append("sliding_windows disagrees with layer_types "
                               "and sliding_window")
            if SLIDING in types[:n] and not w:
                dropped.append("sliding_attention layers without a "
                               "sliding_window")
        mlp = cfg.get("mlp_layer_types")
        if mlp is not None:
            k = cfg.get("first_k_dense_replace", 0)
            if set(mlp) - {"dense", "sparse"}:
                dropped.append(f"mlp_layer_types names "
                               f"{sorted(set(mlp) - {'dense', 'sparse'})}")
            elif list(mlp) != ["dense"] * k + ["sparse"] * (len(mlp) - k):
                dropped.append("mlp_layer_types disagrees with "
                               "first_k_dense_replace")
            if len(mlp) < n:
                dropped.append(f"mlp_layer_types names {len(mlp)} layers, "
                               f"num_hidden_layers is {n}")
        if dropped:
            raise ValueError("ExaoneMoeConfig would drop: "
                             + "; ".join(dropped))


# ---------------------------------------------------------------------------
# weights: groups of parameters by their published names
# ---------------------------------------------------------------------------

def _normal(shape, std, dtype):
    """N(0, std) drawn IN ``dtype`` on the device (no f32 staging)."""
    return Parameter(jax.random.normal(_rng.next_key(), shape, dtype)
                     * jnp.asarray(std, dtype))


class _Group(nn.Layer):
    """Parameters and sub-groups under their state-dict names."""

    def __init__(self, **members):
        super().__init__()
        for name, value in members.items():
            setattr(self, name, value)


def _linear(n_in, n_out, std, dtype):
    return _Group(weight=_normal((n_in, n_out), std, dtype))


def _norm(n, dtype):
    return _Group(weight=Parameter(jnp.ones((n,), dtype)))


def _swiglu(h, m, std, dtype):
    return _Group(gate_proj=_linear(h, m, std, dtype),
                  up_proj=_linear(h, m, std, dtype),
                  down_proj=_linear(m, h, std, dtype))


def _layer(cfg, kind, dtype):
    h, d, std = cfg.hidden_size, cfg.head_dim, cfg.initializer_range
    H, Hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    attn = _Group(q_proj=_linear(h, H * d, std, dtype),
                  k_proj=_linear(h, Hkv * d, std, dtype),
                  v_proj=_linear(h, Hkv * d, std, dtype),
                  o_proj=_linear(H * d, h, std, dtype),
                  q_norm=_norm(d, dtype), k_norm=_norm(d, dtype))
    if kind.mlp == "dense":
        mlp = _swiglu(h, cfg.intermediate_size, std, dtype)
    else:
        e, m = cfg.num_experts, cfg.moe_intermediate_size
        gate = _Group(weight=_normal((h, cfg.router_width), std, dtype))
        # the score-correction bias: zero at a seeded init, float32
        gate.e_score_correction_bias = Parameter(
            jnp.zeros((cfg.router_width,), jnp.float32), trainable=False)
        mlp = _Group(
            gate=gate,
            experts=_Group(gate_proj=_normal((e, h, m), std, dtype),
                           up_proj=_normal((e, h, m), std, dtype),
                           down_proj=_normal((e, m, h), std, dtype)),
            shared_experts=_swiglu(
                h, m * cfg.num_shared_experts, std, dtype))
    return _Group(input_layernorm=_norm(h, dtype), self_attn=attn,
                  post_attention_layernorm=_norm(h, dtype), mlp=mlp)


class ExaoneMoeForCausalLM(nn.Layer):
    """The weights of an EXAONE-MoE decoder (or of one chip's share of
    it), under their published names, and the pytree the serving step
    runs over. ``LLMEngine(model)`` is the one way to run it: there is
    no eager ``forward`` (a chip's share is not a model to train, and
    the plain forward that checks the engine is the benchmark's
    reference, ``benchmark/references/exaone_moe.py``)."""

    def __init__(self, config: ExaoneMoeConfig):
        super().__init__()
        self.config = config
        dtype = to_jax_dtype(config.dtype)
        self._dtype = config.dtype
        h, std = config.hidden_size, config.initializer_range
        kinds = config.layer_kinds()
        self.model = _Group(
            embed_tokens=_Group(weight=_normal(
                (config.vocab_size, h), EMBEDDING_INIT_STD, dtype)),
            layers=nn.LayerList([_layer(config, k, dtype) for k in kinds]),
            norm=_norm(h, dtype))
        self.lm_head = _linear(h, config.vocab_size, std, dtype)

    def serving_params(self):
        """The per-layer pytree ``models.generation.extract_params``
        hands the serving step; a layer's keys follow its kind."""
        layers = []
        for lyr, kind in zip(self.model.layers, self.config.layer_kinds()):
            a, m = lyr.self_attn, lyr.mlp
            p = {"ln1": lyr.input_layernorm.weight,
                 "q": a.q_proj.weight, "k": a.k_proj.weight,
                 "v": a.v_proj.weight, "o": a.o_proj.weight,
                 "q_norm": a.q_norm.weight, "k_norm": a.k_norm.weight,
                 "ln2": lyr.post_attention_layernorm.weight}
            if kind.mlp == "dense":
                p.update(gate=m.gate_proj.weight, up=m.up_proj.weight,
                         down=m.down_proj.weight)
            else:
                s = m.shared_experts
                p.update(router=m.gate.weight,
                         router_bias=m.gate.e_score_correction_bias,
                         experts_gate=m.experts.gate_proj,
                         experts_up=m.experts.up_proj,
                         experts_down=m.experts.down_proj,
                         shared_gate=s.gate_proj.weight,
                         shared_up=s.up_proj.weight,
                         shared_down=s.down_proj.weight)
            layers.append({k: v._data for k, v in p.items()})
        return {"embed": self.model.embed_tokens.weight._data,
                "norm": self.model.norm.weight._data,
                "lm_head": self.lm_head.weight._data, "layers": layers}


__all__ = ["ExaoneMoeConfig", "ExaoneMoeForCausalLM", "FULL", "SLIDING"]
