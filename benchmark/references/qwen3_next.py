"""Plain reference of a Qwen3-Next decoder (Qwen3-Next-80B-A3B's layers),
given the same share of it as the program: straightforward ``jax.numpy``
in float32 under ``jax.default_matmul_precision("highest")``, no kernel,
no cache, no batching.

The layers, from the published ``config.json`` (keys of the configuration
file in backticks). Layer ``i`` is full where ``(i + 1) %
full_attention_interval == 0``, linear otherwise; ``N(x) = RMSNorm(x) (1
+ w)`` (zero-centred) for the two norms of a layer, the q/k head norms
and the final norm.

- linear (Gated DeltaNet): ``x = N1(h)``; ``x W_qkvz`` is, per key head,
  its q and k (``linear_key_head_dim``) and its ``r = linear_num_value_heads
  / linear_num_key_heads`` value heads' v and z (``linear_value_head_dim``):
  the published grouped column layout; ``x W_ba`` per key head its value
  heads' b and a. ``[all q; all k; all v]`` through the causal depthwise
  convolution ``out_t = sum_j w[:, j] in_{t - taps + 1 + j}`` (zeros
  before the sequence) and SiLU; q, k L2-normalised a head (``x /
  sqrt(sum x^2 + 1e-6)``), q times ``dk^-1/2``, a key head repeated over
  its value heads. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``. Per value head, from ``S = 0``, token by token: ``S <-
  exp(g) S``, ``u = beta (v - S^T k)``, ``S <- S + k u^T``, ``o = S^T
  q``. ``o <- w_o RMSNorm(o) silu(z)`` a head (``w_o`` plain, not
  zero-centred); ``h += o W_out``;
- full: ``x = N1(h)``; ``x W_q`` is each head's query and gate; ``q =
  N_q(query)``, ``k = N_k(x W_k)``, ``v = x W_v``; rotary (``rope_theta``)
  on the first ``head_dim x partial_rotary_factor`` dims of each q and k
  head; the token at p attends ``j <= p``, ``softmax(q k^T /
  sqrt(head_dim)) v``, ``heads / kv_heads`` q heads a kv head; times
  ``sigmoid(gate)``; ``h += o W_o``;
- moe: ``x = N2(h)``; ``s = softmax(x W_r)`` over all ``router_width``
  experts, the ``num_experts_per_tok`` largest chosen, ``g_i = s_i /
  sum_chosen s_j`` (``norm_topk_prob``); ``h += sum_{i chosen AND held}
  g_i E_i(x) + sigmoid(x W_sg) E_shared(x)``;
- ``logits = N(h) W_head``.

Departures, noted:

- **the share.** The model holds experts ``[expert_offset, expert_offset
  + num_experts)`` and ``vocab_size`` rows of the vocabulary; this
  reference is given the same weights and, like the program, leaves out
  what the absent experts would add to the sum. It is the reference of
  one chip's share, not of the whole model;
- rotary pairs are adjacent columns (2i, 2i+1) of the rotated part, as
  ``paddle_tpu`` lays them out, not the published half-rotation: equal
  under a fixed permutation of q/k columns, which seeded weights do not
  see;
- the multi-token-prediction block is not run (the configuration's
  ``assumed``).

To fit beside a serving engine: the linear layers run 128 tokens at a
time (projections, convolution with the last inputs carried, the
recurrence, the output), and only as far as the last position asked for;
attention runs a block of 128 queries at a time; one expert's weights
are upcast at a time.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
QB = 128
FULL = "full_attention"


def weights(model):
    """The arrays of a ``Qwen3NextForCausalLM`` by their state-dict
    names; Linear weights are [in, out], experts stacked [held, ...]."""
    sd = {k: v._data for k, v in model.state_dict().items()}
    n = 1 + max(int(k.split(".")[2]) for k in sd
                if k.startswith("model.layers."))
    common = {"ln1": "input_layernorm.weight",
              "ln2": "post_attention_layernorm.weight",
              "router": "mlp.gate.weight",
              "e_gate": "mlp.experts.gate_proj",
              "e_up": "mlp.experts.up_proj",
              "e_down": "mlp.experts.down_proj",
              "gate": "mlp.shared_expert.gate_proj.weight",
              "up": "mlp.shared_expert.up_proj.weight",
              "down": "mlp.shared_expert.down_proj.weight",
              "shared_gate": "mlp.shared_expert_gate.weight"}
    full = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
            "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
            "q_norm": "self_attn.q_norm.weight",
            "k_norm": "self_attn.k_norm.weight"}
    linear = {"qkvz": "linear_attn.in_proj_qkvz.weight",
              "ba": "linear_attn.in_proj_ba.weight",
              "conv": "linear_attn.conv1d.weight",
              "dt_bias": "linear_attn.dt_bias", "A_log": "linear_attn.A_log",
              "out_norm": "linear_attn.norm.weight",
              "out": "linear_attn.out_proj.weight"}
    layers = []
    for i in range(n):
        pre = f"model.layers.{i}."
        names = dict(common, **(linear if pre + linear["qkvz"] in sd
                                else full))
        layers.append({k: sd[pre + v] for k, v in names.items()})
    return {"embed": sd["model.embed_tokens.weight"], "layers": layers,
            "norm": sd["model.norm.weight"], "head": sd["lm_head.weight"]}


def _norm(x, w, eps):
    """The zero-centred RMSNorm: ``RMSNorm(x) (1 + w)``."""
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(F32))


def _rope(x, pos, theta):
    """x [s, heads, d]; positions ``pos`` [s]; adjacent pairs rotate."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv                     # [s, d/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _partial_rope(x, pos, theta, rotary_dim):
    return jnp.concatenate([_rope(x[..., :rotary_dim], pos, theta),
                            x[..., rotary_dim:]], -1)


def _unit(y):
    """L2 normalisation over the last axis, as the published kernel's."""
    return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)


#: attention's output gate
_gate = jax.nn.sigmoid


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps",
                                             "theta", "rotary_dim"))
def _attention(w, h, *, heads, kv_heads, eps, theta, rotary_dim):
    """h [s, hidden] float32 -> h + gated attention, s a multiple of QB."""
    with jax.default_matmul_precision("highest"):
        s, hidden = h.shape
        x = _norm(h, w["ln1"], eps)
        k = (x @ w["k"].astype(F32)).reshape(s, kv_heads, -1)
        v = (x @ w["v"].astype(F32)).reshape(s, kv_heads, -1)
        d = k.shape[-1]
        keys = jnp.arange(s)
        k = _partial_rope(_norm(k, w["k_norm"], eps), keys, theta, rotary_dim)

        def block(args):
            i, xi = args                            # xi [QB, hidden]
            qg = (xi @ w["q"].astype(F32)).reshape(QB, heads, 2 * d)
            pos = i * QB + jnp.arange(QB)
            q = _partial_rope(_norm(qg[..., :d], w["q_norm"], eps), pos,
                              theta, rotary_dim)
            q = q.reshape(QB, kv_heads, heads // kv_heads, d)
            sc = jnp.einsum("qngd,knd->ngqk", q, k) / jnp.sqrt(F32(d))
            ok = keys[None, :] <= pos[:, None]
            p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), -1)
            o = jnp.einsum("ngqk,knd->qngd", p, v).reshape(QB, heads, d)
            o = o * _gate(qg[..., d:])
            return o.reshape(QB, heads * d) @ w["o"].astype(F32)

        a = jax.lax.map(block, (jnp.arange(s // QB),
                                x.reshape(s // QB, QB, hidden)))
        return h + a.reshape(s, hidden)


@functools.partial(jax.jit, static_argnames=("hk", "hv", "dk", "dv", "eps"))
def _linear(w, h, n, *, hk, hv, dk, dv, eps):
    """h [s, hidden] float32 -> h + the Gated DeltaNet mixer over its
    first ``n`` positions (rounded up to a block of QB; s a multiple of
    QB): QB tokens at a time, the state and the convolution's last
    inputs carried from block to block, the recurrence token by token."""
    with jax.default_matmul_precision("highest"):
        hidden = h.shape[1]
        r = hv // hk
        conv_w = w["conv"][:, 0, :].astype(F32)             # [channels, taps]
        taps = conv_w.shape[1]
        def block(b, carry):
            h, S, tail = carry
            hb = jax.lax.dynamic_slice(h, (b * QB, 0), (QB, hidden))
            x = _norm(hb, w["ln1"], eps)
            qkvz = (x @ w["qkvz"].astype(F32)).reshape(QB, hk, -1)
            ba = (x @ w["ba"].astype(F32)).reshape(QB, hk, 2 * r)
            z = qkvz[..., 2 * dk + r * dv:].reshape(QB, hv, dv)
            mixed = jnp.concatenate(
                [qkvz[..., :dk].reshape(QB, -1),
                 qkvz[..., dk:2 * dk].reshape(QB, -1),
                 qkvz[..., 2 * dk:2 * dk + r * dv].reshape(QB, -1)], -1)
            ext = jnp.concatenate([tail, mixed], 0)          # [taps-1+QB, ch]
            conv = sum(ext[j:j + QB] * conv_w[:, j] for j in range(taps))
            conv = jax.nn.silu(conv)
            q = jnp.repeat(_unit(conv[:, :hk * dk].reshape(QB, hk, dk)), r, 1)
            q = q * dk ** -0.5
            k = jnp.repeat(_unit(conv[:, hk * dk:2 * hk * dk]
                                .reshape(QB, hk, dk)), r, 1)
            v = conv[:, 2 * hk * dk:].reshape(QB, hv, dv)
            beta = jax.nn.sigmoid(ba[..., :r].reshape(QB, hv))
            g = -jnp.exp(w["A_log"].astype(F32)) * jax.nn.softplus(
                ba[..., r:].reshape(QB, hv) + w["dt_bias"].astype(F32))

            def token(t, c):
                S, o = c
                S = S * jnp.exp(g[t])[:, None, None]
                kv = jnp.einsum("hkv,hk->hv", S, k[t])
                u = beta[t][:, None] * (v[t] - kv)
                S = S + k[t][:, :, None] * u[:, None, :]
                return S, o.at[t].set(jnp.einsum("hkv,hk->hv", S, q[t]))

            S, o = jax.lax.fori_loop(0, QB, token,
                                     (S, jnp.zeros((QB, hv, dv), F32)))
            o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
            o = o * w["out_norm"].astype(F32) * jax.nn.silu(z)
            hb = hb + o.reshape(QB, hv * dv) @ w["out"].astype(F32)
            return (jax.lax.dynamic_update_slice(h, hb, (b * QB, 0)), S,
                    ext[QB:])

        channels = conv_w.shape[0]
        h, _, _ = jax.lax.fori_loop(
            0, (n + QB - 1) // QB, block,
            (h, jnp.zeros((hv, dk, dv), F32),
             jnp.zeros((taps - 1, channels), F32)))
        return h


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(h, w, *, eps):
    return _norm(h, w, eps)


@jax.jit
def _swiglu(wg, wu, wd, x, scale):
    """One SwiGLU, its weights upcast here; each row times ``scale``."""
    with jax.default_matmul_precision("highest"):
        y = (jax.nn.silu(x @ wg.astype(F32)) * (x @ wu.astype(F32))) \
            @ wd.astype(F32)
        return y * scale[:, None]


@functools.partial(jax.jit, static_argnames=("top_k", "norm"))
def _gates(router, shared_gate, x, *, top_k, norm):
    """([s, router_width]: each token's gate on the experts it chose, 0
    on the others; [s]: the shared expert's gate)."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(x @ router.astype(F32), -1)
        g, idx = jax.lax.top_k(s, top_k)
        if norm:
            g = g / jnp.sum(g, -1, keepdims=True)
        rows = jnp.arange(x.shape[0])[:, None]
        return (jnp.zeros_like(s).at[rows, idx].set(g),
                jax.nn.sigmoid(x @ shared_gate.astype(F32))[:, 0])


def _feed_forward(w, cfg, h):
    x = _normed(h, w["ln2"], eps=float(cfg["rms_norm_eps"]))
    gates, shared = _gates(w["router"], w["shared_gate"], x,
                           top_k=cfg["num_experts_per_tok"],
                           norm=bool(cfg.get("norm_topk_prob", True)))
    h = h + _swiglu(w["gate"], w["up"], w["down"], x, shared)
    first = cfg.get("expert_offset", 0)
    for e in range(w["e_gate"].shape[0]):          # the held experts only
        h = h + _swiglu(w["e_gate"][e], w["e_up"][e], w["e_down"][e], x,
                        gates[:, first + e])
    return h


def _hidden(w, cfg, tokens, n):
    h = w["embed"][jnp.asarray(tokens)].astype(F32)
    eps = float(cfg["rms_norm_eps"])
    interval = cfg["full_attention_interval"]
    head_dim = cfg["head_dim"]
    for i, lw in enumerate(w["layers"]):
        if (i + 1) % interval == 0:
            h = _attention(
                lw, h, heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], eps=eps,
                theta=float(cfg["rope_theta"]),
                rotary_dim=int(head_dim * cfg["partial_rotary_factor"]))
        else:
            h = _linear(lw, h, jnp.asarray(n, jnp.int32),
                        hk=cfg["linear_num_key_heads"],
                        hv=cfg["linear_num_value_heads"],
                        dk=cfg["linear_key_head_dim"],
                        dv=cfg["linear_value_head_dim"], eps=eps)
        h = _feed_forward(lw, cfg, h)
    return h


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(norm_w, head_w, h, rows, *, eps):
    with jax.default_matmul_precision("highest"):
        return _norm(h[rows], norm_w, eps) @ head_w.astype(F32)


def logits_at(w, cfg, tokens, rows):
    """Logits of one sequence ``tokens`` [s] at positions ``rows``. The
    sequence is padded to whole blocks (causal: the pad is inert), and
    the linear layers stop after the last position asked for."""
    tokens = list(tokens) + [0] * (-len(tokens) % QB)
    h = _hidden(w, cfg, tokens, max(rows) + 1)
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
