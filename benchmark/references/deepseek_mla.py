"""Plain reference of a DeepSeek-V3-layer decoder (the language model of
dots.vlm1.inst), given the same share of it as the program:
straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, the published NON-ABSORBED
form, no kernel, no cache, no batching.

The layer, from the published ``config.json`` (keys of the configuration
file in backticks; ``H`` = ``num_attention_heads``):

- ``x = RMSNorm(h)``; ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``); ``q =
  c_q W_qb -> [H, qk_nope_head_dim + qk_rope_head_dim]``, split ``q_nope``,
  ``q_rope``. ``[c_kv ; k_r] = x W_kva`` (``kv_lora_rank`` +
  ``qk_rope_head_dim``); ``c_kv <- RMSNorm(c_kv)``; ``k_r`` is one key
  shared by all heads. EXPANDED a head: ``k_nope_h = c_kv W_uk_h^T``,
  ``v_h = c_kv W_uv_h`` (``W_kvb``'s columns of head ``h``). Rotary on
  ``q_rope`` and ``k_r``. ``score = s (q_nope . k_nope + q_rope . k_r)``
  for keys ``j <= p``; ``s = (nope + rope)^-1/2 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``; ``o = softmax(score) v``; ``h +=
  flatten(o) W_o``.
- rotary, YaRN (``rope_scaling``): ``f_i = theta^(-2i/d)`` over the ``d =
  qk_rope_head_dim`` dims; ``d(r) = d ln(original_max / (2 pi r)) / (2 ln
  theta)``; ``lo = floor(d(beta_fast))``, ``hi = ceil(d(beta_slow))``
  clipped to ``[0, d - 1]``; ``ramp_i = clip((i - lo) / (hi - lo), 0, 1)``;
  ``inv_freq_i = f_i ((1 - ramp_i) + ramp_i / factor)``. cos and sin carry
  ``mscale / mscale_all_dim`` scales' ratio, 1 in the published file.
- ``x = RMSNorm(h)``; dense layers ``h += (silu(x Wg) * x Wu) Wd``; sparse
  layers: ``s = sigmoid(x W_r)`` over all ``router_width`` experts; choice
  scores ``s + b``; the experts form ``n_group`` groups of neighbours, a
  group's score is the sum of its 2 largest choice scores, the
  ``topk_group`` best groups are kept; the ``num_experts_per_tok`` largest
  choice scores of the kept groups are chosen; ``g_i =
  routed_scaling_factor s_i / sum_chosen s_j`` (``norm_topk_prob``); ``h +=
  sum_{i chosen AND held} g_i E_i(x) + E_shared(x)``.
- ``logits = RMSNorm(h) W_head``.

Departures, noted:

- **the share.** The model holds experts ``[expert_offset, expert_offset
  + n_routed_experts)`` and ``vocab_size`` rows of the vocabulary; this
  reference is given the same weights and, like the program, leaves out
  what the absent experts would add to the sum. A token whose kept groups
  lie wholly on other chips gets the shared expert alone here;
- rotary pairs are adjacent columns (2i, 2i+1), as ``paddle_tpu`` lays
  them out; the published code de-interleaves ``q_rope`` / ``k_r`` and
  rotates halves: equal under a fixed permutation of columns, which
  seeded weights do not see;
- the groups not kept are masked with ``-inf``; the published code writes
  0 there, the same choice wherever the kept groups hold
  ``num_experts_per_tok`` positive choice scores (sigmoid scores with
  ``b = 0`` always do);
- the program keeps ``W_kvb`` split a head (``kv_b_proj.k_up [H, nope,
  r]``, ``kv_b_proj.v_up [H, r, v]``); the expansion here multiplies by
  those, column block by column block of the published matrix;
- the multi-token-prediction block and the vision tower are not run
  (``reduced``).

To fit beside a serving engine's 13 GB at 16,640 positions: heads run
``HG`` at a time and queries ``QB`` at a time inside them (the scores of
a group's block are 68 MB), each group's output goes through its rows of
``W_o`` straight into ``h``; the feed-forward runs a block of rows at a
time and upcasts one expert at a time; ``h`` is updated in place.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QB = 128          # queries a block
HG = 8            # heads a group


def weights(model):
    """The arrays of a ``DeepseekMlaForCausalLM`` by their state-dict
    names; Linear weights are [in, out], experts stacked [held, ...]."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    n = 1 + max(int(k.split(".")[2]) for k in sd
                if k.startswith("model.layers."))
    attn = {"ln1": "input_layernorm.weight",
            "q_a": "self_attn.q_a_proj.weight",
            "q_a_norm": "self_attn.q_a_layernorm.weight",
            "q_b": "self_attn.q_b_proj.weight",
            "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
            "kv_a_norm": "self_attn.kv_a_layernorm.weight",
            "k_up": "self_attn.kv_b_proj.k_up",
            "v_up": "self_attn.kv_b_proj.v_up",
            "o": "self_attn.o_proj.weight",
            "ln2": "post_attention_layernorm.weight"}
    dense = {"gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
             "down": "mlp.down_proj.weight"}
    sparse = {"router": "mlp.gate.weight",
              "bias": "mlp.gate.e_score_correction_bias",
              "e_gate": "mlp.experts.gate_proj",
              "e_up": "mlp.experts.up_proj",
              "e_down": "mlp.experts.down_proj",
              "gate": "mlp.shared_experts.gate_proj.weight",
              "up": "mlp.shared_experts.up_proj.weight",
              "down": "mlp.shared_experts.down_proj.weight"}
    layers = []
    for i in range(n):
        pre = f"model.layers.{i}."
        names = dict(attn, **(sparse if pre + sparse["router"] in sd
                              else dense))
        layers.append({k: sd[pre + v] for k, v in names.items()})
    return {"embed": sd["model.embed_tokens.weight"], "layers": layers,
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"]}


# ---------------------------------------------------------------------------
# YaRN, written out (not the program's function)
# ---------------------------------------------------------------------------

def yarn_inv_freq(cfg):
    """float32 ``[qk_rope_head_dim / 2]``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    i = np.arange(d // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / d)
    rs = cfg.get("rope_scaling")
    if rs is None:
        return f.astype(np.float32)
    orig = rs["original_max_position_embeddings"]

    def dim_of(turns):
        return d * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim_of(rs["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(rs["beta_slow"])), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (f * ((1.0 - ramp) + ramp / rs["factor"])).astype(np.float32)


def softmax_scale(cfg):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs is not None and rs.get("mscale_all_dim", 0) and rs["factor"] > 1:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        s *= m * m
    return s


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, inv_freq):
    """x [s, heads, d]; positions 0..s-1; adjacent pairs rotate."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq  # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "scale",
                                             "inv_freq", "hg"),
                   donate_argnums=(1,))
def _attention(w, h, *, heads, eps, scale, inv_freq, hg):
    """h [s, hidden] float32 -> h + attention; s a multiple of QB, heads
    of ``hg``. ``inv_freq`` a tuple (static)."""
    with jax.default_matmul_precision("highest"):
        s = h.shape[0]
        freq = jnp.asarray(inv_freq, F32)
        r = w["kv_a_norm"].shape[0]
        nope = w["k_up"].shape[1]
        x = _rms_norm(h, w["ln1"], eps)
        c_q = _rms_norm(x @ w["q_a"].astype(F32), w["q_a_norm"], eps)
        ckv = x @ w["kv_a"].astype(F32)
        c_kv = _rms_norm(ckv[:, :r], w["kv_a_norm"], eps)
        k_r = _rope(ckv[:, None, r:], freq)[:, 0]              # [s, rope]
        w_qb = w["q_b"].reshape(c_q.shape[1], heads, -1)
        w_o = w["o"].reshape(heads, -1, h.shape[1])
        keys = jnp.arange(s)

        def group(h, g):
            def cut(a, axis):
                return jax.lax.dynamic_slice_in_dim(
                    a, g * hg, hg, axis).astype(F32)
            q = jnp.einsum("sq,qhd->shd", c_q, cut(w_qb, 1))
            q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], freq)
            # the published expansion: this head group's keys and values
            k_nope = jnp.einsum("sr,hnr->shn", c_kv, cut(w["k_up"], 0))
            v = jnp.einsum("sr,hrv->shv", c_kv, cut(w["v_up"], 0))
            wo = cut(w_o, 0).reshape(-1, h.shape[1])           # [hg v, hid]

            def block(i, h):
                qn = jax.lax.dynamic_slice_in_dim(q_nope, i * QB, QB)
                qr = jax.lax.dynamic_slice_in_dim(q_rope, i * QB, QB)
                sc = (jnp.einsum("qhn,khn->hqk", qn, k_nope)
                      + jnp.einsum("qhd,kd->hqk", qr, k_r)) * scale
                pos = i * QB + jnp.arange(QB)
                ok = keys[None, :] <= pos[:, None]
                p = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), -1)
                o = jnp.einsum("hqk,khv->qhv", p, v).reshape(QB, -1)
                hb = jax.lax.dynamic_slice_in_dim(h, i * QB, QB)
                return jax.lax.dynamic_update_slice_in_dim(
                    h, hb + o @ wo, i * QB, 0)
            return jax.lax.fori_loop(0, s // QB, block, h), None

        return jax.lax.scan(group, h, jnp.arange(heads // hg))[0]


def _swiglu(wg, wu, wd, x):
    return (jax.nn.silu(x @ wg.astype(F32)) * (x @ wu.astype(F32))) \
        @ wd.astype(F32)


def _gates(router, bias, x, *, top_k, scaling, norm, n_group, topk_group):
    """[rows, router_width]: each token's gate on the experts it chose,
    0 on the others."""
    s = jax.nn.sigmoid(x @ router.astype(F32))
    choice = s + bias.astype(F32)
    rows = jnp.arange(x.shape[0])[:, None]
    if n_group > 1:
        per = choice.reshape(x.shape[0], n_group, -1)
        best2 = jnp.sort(per, -1)[..., -2:].sum(-1)          # [rows, groups]
        _, kept = jax.lax.top_k(best2, topk_group)
        keep = jnp.zeros(best2.shape, bool).at[rows, kept].set(True)
        choice = jnp.where(jnp.repeat(keep, per.shape[-1], 1), choice,
                           -jnp.inf)
    _, idx = jax.lax.top_k(choice, top_k)
    g = jnp.take_along_axis(s, idx, -1)
    if norm:
        g = g / jnp.sum(g, -1, keepdims=True)
    return jnp.zeros_like(s).at[rows, idx].set(g * scaling)


@functools.partial(jax.jit, static_argnames=(
    "eps", "rb", "top_k", "scaling", "norm", "n_group", "topk_group",
    "first"), donate_argnums=(1,))
def _feed_forward(w, h, *, eps, rb, top_k, scaling, norm, n_group,
                  topk_group, first):
    """h -> h + feed-forward, ``rb`` rows at a time, one expert upcast at
    a time."""
    with jax.default_matmul_precision("highest"):
        def block(i, h):
            hb = jax.lax.dynamic_slice_in_dim(h, i * rb, rb)
            x = _rms_norm(hb, w["ln2"], eps)
            y = _swiglu(w["gate"], w["up"], w["down"], x)  # dense, shared
            if "router" in w:
                gates = _gates(w["router"], w["bias"], x, top_k=top_k,
                               scaling=scaling, norm=norm, n_group=n_group,
                               topk_group=topk_group)

                def expert(e, y):                  # the held experts only
                    g = jax.lax.dynamic_slice_in_dim(gates, first + e, 1, 1)
                    return y + g * _swiglu(w["e_gate"][e], w["e_up"][e],
                                           w["e_down"][e], x)
                y = jax.lax.fori_loop(0, w["e_gate"].shape[0], expert, y)
            return jax.lax.dynamic_update_slice_in_dim(h, hb + y, i * rb, 0)
        return jax.lax.fori_loop(0, h.shape[0] // rb, block, h)


def attention(lw, cfg, h):
    """``h + attention`` of one layer (``h [s, hidden]``, ``s`` a
    multiple of ``QB``): the expanded, per-head form. A float32 device
    array ``h`` is donated: updated in place and not to be used again."""
    heads = cfg["num_attention_heads"]
    return _attention(
        lw, jnp.asarray(h, F32), heads=heads, eps=float(cfg["rms_norm_eps"]),
        scale=float(softmax_scale(cfg)),
        inv_freq=tuple(float(f) for f in yarn_inv_freq(cfg)),
        hg=math.gcd(heads, HG))


def feed_forward(lw, cfg, h):
    """``h + feed-forward`` of one layer, the share's part; ``h`` is
    donated as :func:`attention`'s is."""
    blocks = h.shape[0] // QB
    per = max(g for g in range(1, 17) if blocks % g == 0)
    return _feed_forward(
        lw, jnp.asarray(h, F32), eps=float(cfg["rms_norm_eps"]), rb=per * QB,
        top_k=cfg["num_experts_per_tok"],
        scaling=float(cfg["routed_scaling_factor"]),
        norm=bool(cfg.get("norm_topk_prob", True)),
        n_group=int(cfg.get("n_group", 1)),
        topk_group=int(cfg.get("topk_group", 1)),
        first=int(cfg.get("expert_offset", 0)))


def _hidden(w, cfg, tokens):
    h = w["embed"][jnp.asarray(tokens)].astype(F32)
    for lw in w["layers"]:
        h = attention(lw, cfg, h)
        h = feed_forward(lw, cfg, h)
    return h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, head_w, h, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h[rows], norm_w, eps) @ head_w.astype(F32)


def logits_at(w, cfg, tokens, rows):
    """Logits of one sequence ``tokens`` [s] at positions ``rows``. The
    sequence is padded to whole query blocks (causal: the pad is inert)."""
    tokens = list(tokens) + [0] * (-len(tokens) % QB)
    h = _hidden(w, cfg, tokens)
    return _head(w["norm"], w["head"], h, jnp.asarray(rows),
                 eps=float(cfg["rms_norm_eps"]))


def margins(logits, chosen):
    """How far below its position's best logit each chosen token sits
    (0 where it IS the best). A non-finite logit is an error."""
    lg = jnp.asarray(logits, F32)
    if not bool(jnp.isfinite(lg).all()):
        raise FloatingPointError("non-finite reference logits")
    best = lg.max(-1)
    got = lg[jnp.arange(len(chosen)), jnp.asarray(chosen)]
    return [float(x) for x in best - got]
