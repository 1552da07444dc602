"""DeepSeek-V3's decoder layer (``model_type`` ``deepseek_v3``, and the
language model of ``dots_vlm``: dots.vlm1.inst) — multi-head LATENT
attention over a low-rank cache, YaRN rotary, a group-limited sigmoid
router — built for the serving path.

The layer, from the published ``config.json`` (its keys in backticks):

- attention: pre-norm ``x = RMSNorm(h)``. Queries go through a low rank:
  ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``), ``q = c_q W_qb`` a head
  ``[qk_nope_head_dim ; qk_rope_head_dim]``. Keys and values come from ONE
  compressed row a token: ``[c_kv ; k_r] = x W_kva`` (``kv_lora_rank`` +
  ``qk_rope_head_dim``), ``c_kv <- RMSNorm(c_kv)``, ``k_r`` one rotary key
  shared by every head; a head's ``[k_nope ; v] = c_kv W_kvb``. Rotary
  embedding (YaRN, below) on ``q_rope`` and ``k_r`` only.
  ``score = s (q_nope . k_nope + q_rope . k_r)``, ``s = (nope + rope)^-1/2
  m^2``; ``o = softmax(score) v``; ``h += flatten(o) W_o``.
- what is cached is the row ``[c_kv ; k_r]``, and attention runs in the
  ABSORBED form: ``q_nope . k_nope_j = (q_nope W_uk) . c_kv_j`` and ``o =
  (sum_j p_j c_kv_j) W_uv`` with ``W_kvb`` split a head into ``W_uk`` and
  ``W_uv``: identical mathematics, every head against one key a token
  (``kernels/paged_attention.py::ragged_latent_attention``).
- YaRN (``rope_scaling`` ``type`` ``yarn``): :func:`yarn_inv_freq` keeps
  the high rotary frequencies and divides the low ones by ``factor``, a
  linear ramp between; :func:`yarn_mscale` is the ``m`` above.
- feed-forward: the first ``first_k_dense_replace`` layers one SwiGLU of
  ``intermediate_size``; the others a sigmoid router in float32 whose
  ``router_width`` experts form ``n_group`` groups, the ``topk_group``
  groups with the largest sum of their two best choice scores kept,
  ``num_experts_per_tok`` chosen among those, gates normalised and scaled
  by ``routed_scaling_factor``; experts SwiGLU of
  ``moe_intermediate_size`` plus one shared expert
  (``nn/moe_dropless.py``).

**A chip's share**, as ``models/exaone_moe.py`` states it: the model
holds ``n_routed_experts`` experts ``[expert_offset, expert_offset +
n_routed_experts)`` of the ``router_width`` the router scores, and
``vocab_size`` rows of the vocabulary.

``kv_b_proj`` is kept split a head, ``k_up [heads, nope, kv_lora_rank]``
(``W_uk``) and ``v_up [heads, kv_lora_rank, v]`` (``W_uv``), the shapes
the absorbed step multiplies by: a seeded draw does not see the
difference, a published checkpoint would be cut that way once at load,
and a second copy of it (34 MB a layer) is not held.

The vision tower of ``dots_vlm`` is not here: the model takes token ids.
``check_published`` refuses a file whose settings this model would
silently drop.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.dtype import to_jax_dtype
from ..nn.layer.layers import Parameter
from .exaone_moe import (EMBEDDING_INIT_STD, _Group, _linear, _norm,
                         _normal, _swiglu)
from .generation import LayerKind


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def yarn_inv_freq(dim, theta, factor, original_max_position_embeddings,
                  beta_fast=32, beta_slow=1):
    """The ``dim // 2`` rotary frequencies under YaRN (numpy float32).
    ``f_i = theta^(-2i/dim)``; pair ``i`` turns ``original_max /
    (2 pi / f_i)`` times over the original length, and ``d(r) = dim
    ln(original_max / (2 pi r)) / (2 ln theta)`` is the pair that turns
    ``r`` times. Pairs under ``lo = floor(d(beta_fast))`` keep ``f_i``
    (high frequencies), pairs over ``hi = ceil(d(beta_slow))`` get ``f_i /
    factor`` (interpolated), a linear ramp between. ``lo`` and ``hi`` are
    clipped to ``[0, dim - 1]``, as the published code clips them."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def d(r):
        return dim * math.log(original_max_position_embeddings
                              / (r * 2 * math.pi)) / (2 * math.log(theta))
    lo = max(math.floor(d(beta_fast)), 0)
    hi = min(math.ceil(d(beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001             # the published code's guard
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (f * ((1.0 - ramp) + ramp / factor)).astype(np.float32)


def yarn_mscale(factor, mscale=1.0):
    """``0.1 mscale ln(factor) + 1`` (1 where ``factor <= 1``)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def _yarn_defaults():
    return {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"}


@dataclass
class DeepseekMlaConfig:
    vocab_size: int = 129280            # rows of the vocabulary HELD
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: None = plain rotary by ``rope_theta``; else a ``yarn`` group
    rope_scaling: dict | None = field(default_factory=_yarn_defaults)
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    n_routed_experts: int = 256         # experts HELD
    #: experts the router scores (the published ``n_routed_experts``);
    #: None = ``n_routed_experts``, the uncut model
    router_width: int | None = None
    #: id, among ``router_width``, of the first expert held
    expert_offset: int = 0
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.router_width is None:
            self.router_width = self.n_routed_experts
        name = type(self).__name__
        if self.expert_offset < 0 or \
                self.expert_offset + self.n_routed_experts \
                > self.router_width:
            raise ValueError(
                f"{name}: experts [{self.expert_offset}, "
                f"{self.expert_offset + self.n_routed_experts}) are not "
                f"among the router's {self.router_width}")
        if self.router_width % self.n_group:
            raise ValueError(
                f"{name}: {self.router_width} experts do not form "
                f"{self.n_group} equal groups")
        if not 1 <= self.topk_group <= self.n_group:
            raise ValueError(
                f"{name}: topk_group {self.topk_group} of {self.n_group} "
                f"groups")
        kept = self.topk_group * (self.router_width // self.n_group)
        if self.num_experts_per_tok > kept:
            raise ValueError(
                f"{name}: {self.num_experts_per_tok} experts a token of "
                f"the {kept} in {self.topk_group} kept groups")
        if self.n_group > 1 and self.router_width // self.n_group < 2:
            raise ValueError(
                f"{name}: a group's score is the sum of its two best "
                f"experts; groups of {self.router_width // self.n_group}")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"{name}: qk_rope_head_dim "
                             f"{self.qk_rope_head_dim} is odd")
        rs = self.rope_scaling
        if rs is not None and rs.get("type") != "yarn":
            raise ValueError(f"{name}: rope_scaling type "
                             f"{rs.get('type')!r} is not 'yarn'")

    @property
    def mlp_layer_types(self):
        k, f = self.first_k_dense_replace, self.moe_layer_freq
        return ["sparse" if i >= k and i % f == 0 else "dense"
                for i in range(self.num_hidden_layers)]

    @property
    def latent_width(self) -> int:
        """Values of the row a token caches: ``[c_kv ; k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """The row as the pool holds it: ``latent_width`` padded to whole
        128-lane tiles (576 -> 640). The chip lays a 576-wide bf16 row
        out in 640 lanes whatever is declared (its HBM tiling is (8,
        128)), and its compiler slices such a row only by whole tiles,
        so the padding is stated, not hidden: a token holds this many
        values a layer."""
        return -(-self.latent_width // 128) * 128

    def rope_inv_freq(self):
        """The rotary frequencies of the ``qk_rope_head_dim`` dims."""
        d, rs = self.qk_rope_head_dim, self.rope_scaling
        if rs is None:
            return (float(self.rope_theta)
                    ** (-np.arange(0, d, 2, dtype=np.float64) / d)
                    ).astype(np.float32)
        return yarn_inv_freq(
            d, float(self.rope_theta), rs["factor"],
            rs["original_max_position_embeddings"],
            rs.get("beta_fast", 32), rs.get("beta_slow", 1))

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-1/2``, times YaRN's ``m^2``."""
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs is not None and rs.get("mscale_all_dim", 0):
            s *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return s

    def layer_kinds(self):
        """``models.generation.LayerKind`` of each layer run."""
        return tuple(LayerKind(latent=True, mlp=m)
                     for m in self.mlp_layer_types)

    @classmethod
    def check_published(cls, cfg):
        """Refuse, each by name, what this model would silently drop of
        a published file ``cfg`` (the whole file as a dict)."""
        dropped = []
        reduced = cfg.get("reduced") or {}
        rs = cfg.get("rope_scaling")
        if rs is not None:
            if rs.get("type") != "yarn":
                dropped.append(f"rope_scaling.type {rs.get('type')!r}; "
                               f"this path scales rotary by YaRN alone")
            elif rs.get("mscale", 1) != rs.get("mscale_all_dim", 0):
                dropped.append(
                    f"rope_scaling.mscale {rs.get('mscale', 1)} != "
                    f"mscale_all_dim {rs.get('mscale_all_dim', 0)}: cos "
                    f"and sin would carry their ratio, which this path "
                    f"does not apply")
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
        if cfg.get("topk_method", "noaux_tc") != "noaux_tc":
            dropped.append(f"topk_method {cfg['topk_method']!r}; this "
                           f"router picks by noaux_tc (score + bias, "
                           f"group-limited)")
        if cfg.get("n_shared_experts", 1) != 1:
            dropped.append(f"n_shared_experts is "
                           f"{cfg['n_shared_experts']}; this layer has one "
                           f"shared expert")
        if cfg.get("q_lora_rank") is None:
            dropped.append("q_lora_rank is null; this path projects "
                           "queries through a low rank")
        if cfg.get("moe_layer_freq", 1) != 1:
            dropped.append(f"moe_layer_freq is {cfg['moe_layer_freq']}; "
                           f"every layer after the dense ones is sparse "
                           f"here")
        if cfg.get("num_nextn_predict_layers", 0):
            dropped.append("num_nextn_predict_layers is set; this path "
                           "runs no multi-token-prediction block")
        if cfg.get("tie_word_embeddings"):
            dropped.append("tie_word_embeddings is true; this model has "
                           "its own head")
        if cfg.get("num_key_value_heads",
                   cfg["num_attention_heads"]) != cfg["num_attention_heads"]:
            dropped.append("num_key_value_heads differs from "
                           "num_attention_heads; every head expands its "
                           "own key and value from the latent row")
        if cfg.get("ep_size", 1) != 1:
            dropped.append(f"ep_size is {cfg['ep_size']}; the share is "
                           f"stated by expert_offset / router_width, and "
                           f"no exchange runs")
        if cfg.get("vision_config") is not None \
                and "vision_tower" not in reduced \
                and "vision_config" not in reduced:
            dropped.append("vision_config is set and not declared "
                           "reduced; this model takes token ids, no "
                           "vision tower runs")
        mlp = cfg.get("mlp_layer_types")
        if mlp is not None:
            n, k = cfg["num_hidden_layers"], cfg.get(
                "first_k_dense_replace", 0)
            if list(mlp)[:n] != ["dense"] * min(k, n) \
                    + ["sparse"] * max(n - k, 0):
                dropped.append("mlp_layer_types disagrees with "
                               "first_k_dense_replace")
        if dropped:
            raise ValueError(f"{cls.__name__} would drop: "
                             + "; ".join(dropped))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _layer(cfg, kind, dtype):
    h, std = cfg.hidden_size, cfg.initializer_range
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, rope, v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    attn = _Group(
        q_a_proj=_linear(h, cfg.q_lora_rank, std, dtype),
        q_a_layernorm=_norm(cfg.q_lora_rank, dtype),
        q_b_proj=_linear(cfg.q_lora_rank, H * (nope + rope), std, dtype),
        kv_a_proj_with_mqa=_linear(h, r + rope, std, dtype),
        kv_a_layernorm=_norm(r, dtype),
        # the published kv_b_proj [r, H * (nope + v)], split a head
        kv_b_proj=_Group(k_up=_normal((H, nope, r), std, dtype),
                         v_up=_normal((H, r, v), std, dtype)),
        o_proj=_linear(H * v, h, std, dtype))
    if kind.mlp == "dense":
        mlp = _swiglu(h, cfg.intermediate_size, std, dtype)
    else:
        e, m = cfg.n_routed_experts, cfg.moe_intermediate_size
        gate = _Group(weight=_normal((h, cfg.router_width), std, dtype))
        gate.e_score_correction_bias = Parameter(
            jnp.zeros((cfg.router_width,), jnp.float32), trainable=False)
        mlp = _Group(
            gate=gate,
            experts=_Group(gate_proj=_normal((e, h, m), std, dtype),
                           up_proj=_normal((e, h, m), std, dtype),
                           down_proj=_normal((e, m, h), std, dtype)),
            shared_experts=_swiglu(h, m * cfg.n_shared_experts, std, dtype))
    return _Group(input_layernorm=_norm(h, dtype), self_attn=attn,
                  post_attention_layernorm=_norm(h, dtype), mlp=mlp)


class DeepseekMlaForCausalLM(nn.Layer):
    """The weights of a DeepSeek-V3-layer decoder (or of one chip's share
    of it) and the pytree the serving step runs over. ``LLMEngine(model)``
    is the one way to run it, as ``ExaoneMoeForCausalLM``: the plain
    forward that checks the engine is the benchmark's reference,
    ``benchmark/references/deepseek_mla.py``."""

    def __init__(self, config: DeepseekMlaConfig):
        super().__init__()
        self.config = config
        dtype = to_jax_dtype(config.dtype)
        self._dtype = config.dtype
        h, std = config.hidden_size, config.initializer_range
        self.model = _Group(
            embed_tokens=_Group(weight=_normal(
                (config.vocab_size, h), EMBEDDING_INIT_STD, dtype)),
            layers=nn.LayerList([_layer(config, k, dtype)
                                 for k in config.layer_kinds()]),
            norm=_norm(h, dtype))
        self.lm_head = _linear(h, config.vocab_size, std, dtype)

    def serving_params(self):
        """The per-layer pytree ``models.generation.extract_params``
        hands the serving step; ``w_uk [heads, nope, r]`` and ``w_uv
        [heads, r, v]`` are the parameters themselves, in the shape the
        absorbed step multiplies by."""
        layers = []
        for lyr, kind in zip(self.model.layers, self.config.layer_kinds()):
            a, m = lyr.self_attn, lyr.mlp
            p = {"ln1": lyr.input_layernorm.weight,
                 "q_a": a.q_a_proj.weight, "q_a_norm": a.q_a_layernorm.weight,
                 "q_b": a.q_b_proj.weight,
                 "kv_a": a.kv_a_proj_with_mqa.weight,
                 "kv_a_norm": a.kv_a_layernorm.weight,
                 "w_uk": a.kv_b_proj.k_up, "w_uv": a.kv_b_proj.v_up,
                 "o": a.o_proj.weight,
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


__all__ = ["DeepseekMlaConfig", "DeepseekMlaForCausalLM", "yarn_inv_freq",
           "yarn_mscale"]
