"""Plain reference of a Llama-style dense decoder: RMSNorm, rotary
embedding, grouped-query softmax attention, SwiGLU. Straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``
(on a TPU an f32 matmul otherwise runs in lower precision): no kernel,
no cache, no batching, one layer's weights upcast at a time so that it
fits beside a serving pool.

Departure from the published models, noted: rotary pairs are adjacent
columns (2i, 2i+1), as ``paddle_tpu`` lays them out, not the published
half-rotation (i, i + d/2). The two are equal under a fixed permutation
of q/k columns, which seeded weights do not see.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def weights(model):
    """The arrays of a ``LlamaForCausalLM``, read by their state-dict
    names. Linear weights are [in, out]."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    n = 1 + max(int(k.split(".")[2]) for k in sd
                if k.startswith("model.layers."))
    names = {"ln1": "input_layernorm", "q": "self_attn.q_proj",
             "k": "self_attn.k_proj", "v": "self_attn.v_proj",
             "o": "self_attn.o_proj", "ln2": "post_attention_layernorm",
             "gate": "mlp.gate_proj", "up": "mlp.up_proj",
             "down": "mlp.down_proj"}
    layers = [{k: sd[f"model.layers.{i}.{v}.weight"]
               for k, v in names.items()} for i in range(n)]
    embed = sd["model.embed_tokens.weight"]
    return {"embed": embed, "layers": layers, "norm": sd["model.norm.weight"],
            "head": sd.get("lm_head.weight")}     # None: tied to embed


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
                                             "theta"))
def _layer(w, h, *, heads, kv_heads, eps, theta):
    """h [s, hidden] float32 -> [s, hidden]."""
    with jax.default_matmul_precision("highest"):
        s = h.shape[0]
        x = _rms_norm(h, w["ln1"], eps)
        q = (x @ w["q"].astype(F32)).reshape(s, heads, -1)
        k = (x @ w["k"].astype(F32)).reshape(s, kv_heads, -1)
        v = (x @ w["v"].astype(F32)).reshape(s, kv_heads, -1)
        d = q.shape[-1]
        q, k = _rope(q, theta), _rope(k, theta)
        g = heads // kv_heads
        q = q.reshape(s, kv_heads, g, d)
        sc = jnp.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(F32(d))
        causal = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        a = jnp.einsum("ngqk,knd->qngd", p, v).reshape(s, heads * d)
        h = h + a @ w["o"].astype(F32)
        x = _rms_norm(h, w["ln2"], eps)
        up = jax.nn.silu(x @ w["gate"].astype(F32)) * (x @ w["up"].astype(F32))
        return h + up @ w["down"].astype(F32)


@functools.partial(jax.jit, static_argnames=("eps", "tied"))
def _head(norm_w, head_w, h, rows, *, eps, tied):
    """Logits [len(rows), vocab] of the hidden rows ``rows``."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(h[rows], norm_w, eps)
        hw = head_w.astype(F32)
        return x @ (hw.T if tied else hw)


def _hidden(w, cfg, tokens):
    h = w["embed"][jnp.asarray(tokens)].astype(F32)
    for lw in w["layers"]:
        h = _layer(lw, h, heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"],
                   eps=float(cfg["rms_norm_eps"]),
                   theta=float(cfg["rope_theta"]))
    return h


def logits_at(w, cfg, tokens, rows):
    """Logits of one sequence ``tokens`` [s] at positions ``rows``."""
    h = _hidden(w, cfg, tokens)
    tied = w["head"] is None
    return _head(w["norm"], w["embed"] if tied else w["head"], h,
                 jnp.asarray(rows), eps=float(cfg["rms_norm_eps"]),
                 tied=tied)


def loss(w, cfg, tokens):
    """Mean next-token cross entropy of a batch ``tokens`` [b, s], one
    sequence at a time (position i predicts token i+1)."""
    total = 0.0
    for seq in tokens:
        s = len(seq)
        lg = logits_at(w, cfg, seq, list(range(s - 1)))
        lse = jax.nn.logsumexp(lg, -1)
        picked = lg[jnp.arange(s - 1), jnp.asarray(seq[1:])]
        total += float(jnp.mean(lse - picked))
    return total / len(tokens)


def margins(logits, chosen):
    """How far below its position's best logit each chosen token sits
    (0 where it IS the best). A non-finite logit is an error."""
    lg = jnp.asarray(logits, F32)
    if not bool(jnp.isfinite(lg).all()):
        raise FloatingPointError("non-finite reference logits")
    best = lg.max(-1)
    got = lg[jnp.arange(len(chosen)), jnp.asarray(chosen)]
    return [float(x) for x in best - got]
