"""Plain reference of an EXAONE-MoE decoder (K-EXAONE-236B-A23B's layer),
given the same share of it as the program: straightforward ``jax.numpy``
in float32 under ``jax.default_matmul_precision("highest")``, no kernel,
no cache, no batching.

The layer, from the published ``config.json`` (keys of the configuration
file in backticks):

- ``x = RMSNorm(h)``; ``q = x Wq -> [heads, head_dim]``, ``k``, ``v ->
  [kv_heads, head_dim]``; q and k RMS-normalised over the ``head_dim`` of
  each head; rotary embedding on q and k where ``layer_types[i]`` is
  ``sliding_attention``, none on ``full_attention`` layers; the token at
  position p attends keys j with ``p - sliding_window < j <= p`` on a
  sliding layer, ``j <= p`` on a full one; ``softmax(q k^T /
  sqrt(head_dim)) v``, ``heads / kv_heads`` q heads a kv head; ``h += o
  Wo``;
- ``x = RMSNorm(h)``; ``mlp_layer_types[i]`` ``dense``: ``h +=
  (silu(x Wg) * x Wu) Wd``; ``sparse``: ``s = sigmoid(x Wr)`` over all
  ``router_width`` experts, the ``num_experts_per_tok`` largest of ``s +
  b`` chosen, ``g_i = routed_scaling_factor * s_i / sum_chosen s_j``
  (``norm_topk_prob``), ``h += sum_{i chosen AND held} g_i E_i(x) +
  E_shared(x)``;
- ``logits = RMSNorm(h) W_head``.

Departures, noted:

- **the share.** The model holds experts ``[expert_offset, expert_offset
  + num_experts)`` and ``vocab_size`` rows of the vocabulary; this
  reference is given the same weights and, like the program, leaves out
  what the absent experts would add to the sum. It is the reference of
  one chip's share, not of the whole model;
- rotary pairs are adjacent columns (2i, 2i+1), as ``paddle_tpu`` lays
  them out, not the published half-rotation: equal under a fixed
  permutation of q/k columns, which seeded weights do not see;
- the multi-token-prediction block is not run (``reduced``).

To fit beside a serving engine's 12.8 GB: one expert's weights are upcast
at a time, and attention runs a block of 128 queries at a time (the
scores of a 4,352-token sequence are 143 MB a block, not 4.8 GB).
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QB = 128
SLIDING = "sliding_attention"


def weights(model):
    """The arrays of an ``ExaoneMoeForCausalLM`` by their state-dict
    names; Linear weights are [in, out], experts stacked [held, ...]."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    n = 1 + max(int(k.split(".")[2]) for k in sd
                if k.startswith("model.layers."))
    attn = {"ln1": "input_layernorm.weight",
            "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
            "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
            "q_norm": "self_attn.q_norm.weight",
            "k_norm": "self_attn.k_norm.weight",
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


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def _rope(x, theta):
    """x [s, heads, d]; positions 0..s-1; adjacent pairs rotate."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv            # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "window", "rope"))
def _attention(w, h, *, heads, kv_heads, eps, theta, window, rope):
    """h [s, hidden] float32 -> h + attention, s a multiple of QB."""
    with jax.default_matmul_precision("highest"):
        s = h.shape[0]
        x = _rms_norm(h, w["ln1"], eps)
        q = (x @ w["q"].astype(F32)).reshape(s, heads, -1)
        k = (x @ w["k"].astype(F32)).reshape(s, kv_heads, -1)
        v = (x @ w["v"].astype(F32)).reshape(s, kv_heads, -1)
        d = q.shape[-1]
        q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
        if rope:
            q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(s // QB, QB, kv_heads, heads // kv_heads, d)
        keys = jnp.arange(s)

        def block(args):
            i, qi = args                            # qi [QB, n, g, d]
            sc = jnp.einsum("qngd,knd->ngqk", qi, k) / jnp.sqrt(F32(d))
            pos = i * QB + jnp.arange(QB)
            ok = keys[None, :] <= pos[:, None]
            if window is not None:
                ok &= keys[None, :] > pos[:, None] - window
            p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), -1)
            return jnp.einsum("ngqk,knd->qngd", p, v).reshape(QB, heads * d)

        a = jax.lax.map(block, (jnp.arange(s // QB), q))
        return h + a.reshape(s, heads * d) @ w["o"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(h, w, *, eps):
    return _rms_norm(h, w, eps)


@jax.jit
def _swiglu(wg, wu, wd, x, scale):
    """One SwiGLU, its weights upcast here; each row times ``scale``."""
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(x @ wg.astype(F32)) * (x @ wu.astype(F32))) \
            @ wd.astype(F32)
        return y * scale[:, None]


@functools.partial(jax.jit, static_argnames=("top_k", "scaling", "norm"))
def _gates(router, bias, x, *, top_k, scaling, norm):
    """[s, router_width]: each token's gate on the experts it chose, 0
    on the others."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ router.astype(F32))
        _, idx = jax.lax.top_k(s + bias.astype(F32), top_k)
        g = jnp.take_along_axis(s, idx, -1)
        if norm:
            g = g / jnp.sum(g, -1, keepdims=True)
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.zeros_like(s).at[rows, idx].set(g * scaling)


def _feed_forward(w, cfg, h):
    x = _normed(h, w["ln2"], eps=float(cfg["rms_norm_eps"]))
    ones = jnp.ones((x.shape[0],), F32)
    h = h + _swiglu(w["gate"], w["up"], w["down"], x, ones)   # dense, shared
    if "router" not in w:
        return h
    gates = _gates(w["router"], w["bias"], x,
                   top_k=cfg["num_experts_per_tok"],
                   scaling=float(cfg["routed_scaling_factor"]),
                   norm=bool(cfg.get("norm_topk_prob", True)))
    first = cfg.get("expert_offset", 0)
    for e in range(w["e_gate"].shape[0]):          # the held experts only
        h = h + _swiglu(w["e_gate"][e], w["e_up"][e], w["e_down"][e], x,
                        gates[:, first + e])
    return h


def _hidden(w, cfg, tokens):
    h = w["embed"][jnp.asarray(tokens)].astype(F32)
    for i, lw in enumerate(w["layers"]):
        sliding = cfg["layer_types"][i] == SLIDING
        h = _attention(
            lw, h, heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"],
            eps=float(cfg["rms_norm_eps"]),
            theta=float(cfg["rope_parameters"]["rope_theta"]),
            window=int(cfg["sliding_window"]) if sliding else None,
            rope=sliding)
        h = _feed_forward(lw, cfg, h)
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
