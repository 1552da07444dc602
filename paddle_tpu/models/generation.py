"""Autoregressive generation engine: prefill + KV-cache decode, fully jitted.

TPU-native analog of the reference's decode stack (reference: C12 kernels
masked_multihead_attention paddle/phi/kernels/fusion/gpu/
masked_multihead_attention_kernel.cu (single-token decode against cached
KV) and block_multi_head_attention (paged KV); generation loop
python/paddle/generation-style APIs). Design:

- the model's weights are extracted ONCE into a pure pytree;
- ``prefill`` (whole prompt, causal flash path) and ``decode_step`` (one
  token against the static-shape KV cache via dynamic_update_slice) are
  two cached XLA executables — the decode step is the latency-critical
  kernel, all fused by XLA (qkv proj + rope + attention + mlp in one
  program, no per-op dispatch);
- the cache is preallocated [L, B, max_len, Hkv, d] — static shapes, no
  re-compilation as generation proceeds (the role of the reference's
  paged/block KV layout is played by the static ring of slots).

Sampling: greedy / temperature / top-k / top-p, computed in-graph.

Serving contract: paddle_tpu/serving/engine.py reuses
``_rope``/``_rms_norm``/``_wmat``/``_logits`` and ``extract_params`` so
the continuous-batching engine's math is THIS module's math — the greedy
token-identity between ``LLMEngine`` and sequential ``Generator.generate``
(tests/test_serving_engine.py) depends on these bodies staying shared.
The engine's ragged step (decode rows + prefill chunks in one launch)
runs attention through the ragged Pallas kernel instead of ``_block``'s
dense causal path, but projections, rope, norms and logits are these
functions — change them here and the ragged step body together.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.flags import GLOBAL_FLAGS, define_flag
from ..core.tensor import Tensor


def _check_burst_tokens(v):
    if int(v) < 1:
        raise ValueError(
            f"FLAGS_decode_burst_tokens must be >= 1, got {v!r}")


define_flag("decode_burst_tokens", int, 1,
            "generation burst length: how many decode iterations run "
            "on-device inside one jitted lax.while_loop (sample -> KV "
            "append -> EOS/length gate all in-graph) before the host "
            "re-syncs — one host dispatch per burst instead of one per "
            "token (Generator.generate and serving LLMEngine). 1 (the "
            "default) is the per-token path, bit-identical to the "
            "pre-burst engine", on_set=_check_burst_tokens)


_MEGAKERNEL_SCOPES = ("layer", "model")


def _check_megakernel_scope(v):
    if v not in _MEGAKERNEL_SCOPES:
        raise ValueError(
            f"FLAGS_decode_megakernel_scope must be one of "
            f"{_MEGAKERNEL_SCOPES}, got {v!r}")


define_flag("decode_megakernel_scope", str, "layer",
            "where the decode layer loop lives: 'layer' (the default) "
            "unrolls L fused-layer launches per token — today's path, "
            "bit-identical to every prior release; 'model' moves the "
            "loop INSIDE the traced program as a lax.scan over "
            "LayerStack-stacked [L, ...] weights and KV pools "
            "(kernels/decode_megakernel.fused_decode_model), so a "
            "decode step is ONE launch per token and the on-device "
            "burst while_loop is one launch per burst. Token output is "
            "bitwise identical between scopes (gated by "
            "tests/test_decode_megakernel.py); jit/hlo_forensics.py "
            "launch_stats holds the launch-count collapse",
            on_set=_check_megakernel_scope)


def resolve_megakernel_scope(scope):
    """Validate an explicit scope or fall back to
    ``FLAGS_decode_megakernel_scope`` (Generator/LLMEngine ctor knob)."""
    if scope is None:
        scope = str(GLOBAL_FLAGS.get("decode_megakernel_scope"))
    _check_megakernel_scope(scope)
    return scope


_PREFILL_MEGAKERNEL_MODES = ("unfused", "fused")


def _check_prefill_megakernel(v):
    if v not in _PREFILL_MEGAKERNEL_MODES:
        raise ValueError(
            f"FLAGS_prefill_megakernel must be one of "
            f"{_PREFILL_MEGAKERNEL_MODES}, got {v!r}")


define_flag("prefill_megakernel", str, "unfused",
            "the ragged prefill chain's launch shape: 'unfused' (the "
            "default) keeps today's per-projection layer bodies — "
            "bit-identical to every prior release; 'fused' routes the "
            "whole ragged prologue/epilogue chain (rms_norm -> fused qkv "
            "projection -> rope at per-row positions -> KV append -> "
            "ragged paged attention -> o-proj -> rms_norm -> swiglu) "
            "through kernels/prefill_megakernel.fused_prefill_layer: the "
            "layer-invariant prologue (rope phase tables, page/slot "
            "scatter map, attention block-row map) is computed ONCE per "
            "step and the projections run as fused concat-dots, so a "
            "prefill chunk costs O(1) launches at model scope. Token "
            "output is bitwise identical between modes (gated by "
            "tests/test_prefill_megakernel.py)",
            on_set=_check_prefill_megakernel)


def resolve_prefill_megakernel(mode):
    """Validate an explicit prefill launch shape or fall back to
    ``FLAGS_prefill_megakernel`` (Generator/LLMEngine ctor knob)."""
    if mode is None:
        mode = str(GLOBAL_FLAGS.get("prefill_megakernel"))
    _check_prefill_megakernel(mode)
    return mode


#: host->device dispatch forensics for the burst gate
#: (tests/test_decode_megakernel.py): every jitted launch generate()
#: issues — prefill, per-token decode, or burst — bumps this counter, so
#: a generation burst of N tokens must cost O(1) increments where the
#: per-token path costs >= N (the optimizer/serving dispatch-gate
#: discipline).
_HOST_DISPATCH = {"count": 0}


def host_dispatch_count() -> int:
    return _HOST_DISPATCH["count"]


# ---------------------------------------------------------------------------
# pure forward math (mirrors models/llama.py layers; parity-tested)
# ---------------------------------------------------------------------------

def _rope(x, pos, theta, head_dim, inv_freq=None):
    """x: [b, s, h, d]; pos: [b, s] absolute positions.

    Interleaved adjacent-pair convention — must match the training
    model's op exactly (nn/functional/attention.py _rope_reference).
    ``inv_freq [d/2]``: the frequencies where they are not ``theta``'s
    own (a scaled rotary: ``models/deepseek_mla.py::yarn_inv_freq``).
    """
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                               dtype=jnp.float32)
                                    / head_dim))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq       # [b, s, d/2]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1 = x[..., ::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def _rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _attn_scores(q, k, mask):
    # q: [b, sq, H, d]; k: [b, sk, H, d] -> [b, H, sq, sk]
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    s = jnp.where(mask, s, -1e30)
    return jax.nn.softmax(s.astype(jnp.float32), -1).astype(q.dtype)


def _repeat_kv(x, rep):
    if rep == 1:
        return x
    return jnp.repeat(x, rep, axis=2)


def _wmat(x, w, lora=None):
    """Projection matmul over a raw array OR a low-bit serving weight
    (quantization.QuantizedWeight -> the fused dequant-matmul kernel).
    Every projection in the prefill/decode bodies routes through here so
    ``quantize_params`` pytrees run fully jitted — the dequant happens in
    the kernel prologue, never as a per-token eager dispatch.

    ``lora=(A, B, slots)`` adds the batched multi-tenant LoRA delta
    (paddle_tpu.tenancy): A ``[n_slots, r, d_in]``, B ``[n_slots,
    d_out, r]``, slots ``[t]`` int32 per-row adapter-slot ids. Each row
    computes ``base(x) + (x @ A[slot].T) @ B[slot].T`` via a batched
    gather — the slot vector is DATA, so rows wearing different
    adapters (or none: slot 0 is all-zero = the base model, bitwise)
    share one trace of one executable. The delta runs in fp over the
    (possibly int8/int4-dequant) base matmul output.
    """
    from ..quantization.low_bit import matmul
    y = matmul(x, w)
    if lora is not None:
        y = y + _lora_delta(x, lora).astype(y.dtype)
    return y


def _lora_delta(x, lora):
    """The batched multi-tenant LoRA delta of :func:`_wmat`'s ``lora``
    leg, exposed so the fused prefill body (which computes the base
    projection as ONE concat-dot) can add the same per-projection delta
    to a slice of the fused output — slice-of-concat-dot plus this
    delta is bitwise the per-projection ``_wmat`` result."""
    A, B, slots = lora
    if x.ndim == 2:                       # [t, d_in] token-major
        xa = jnp.einsum("td,trd->tr", x.astype(jnp.float32),
                        A[slots].astype(jnp.float32))
        return jnp.einsum("tr,tor->to", xa,
                          B[slots].astype(jnp.float32))
    # [b, t, d_in], slots [t]
    xa = jnp.einsum("btd,trd->btr", x.astype(jnp.float32),
                    A[slots].astype(jnp.float32))
    return jnp.einsum("btr,tor->bto", xa,
                      B[slots].astype(jnp.float32))


_STACKED_LAYER_KEYS = {
    "ln1": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight",
    "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight",
    "o": "self_attn.o_proj.weight",
    "ln2": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight",
    "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight",
}


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one decoder layer is, as static data of its config: the
    serving step's layer body (serving/spec_decode.py) reads it at trace
    time, so layers of different kinds are one executable, not modes.
    The default is a Llama layer."""
    #: keys a query sees, itself included; None = every key before it
    window: int | None = None
    #: rotary embedding on q and k
    rope: bool = True
    #: RMSNorm over each q and k head (weights ``q_norm``/``k_norm``)
    qk_norm: bool = False
    #: "dense" SwiGLU, or "sparse": routed experts + a shared expert
    mlp: str = "dense"
    #: latent attention: a token caches ONE compressed row every head
    #: reads (its sizes and rotary are the config's), not keys and
    #: values a kv head; the pool is then a latent one
    #: (serving/kv_cache.py ``latent_row``)
    latent: bool = False


def layer_kinds(cfg):
    """The kind of each of ``cfg``'s layers: the config's own
    ``layer_kinds()`` where it has one, else all Llama layers."""
    own = getattr(cfg, "layer_kinds", None)
    if own is not None:
        return tuple(own())
    return (LayerKind(),) * cfg.num_hidden_layers


def extract_params(model):
    """Pull a causal LM's weights into a pure pytree. A model whose
    layers differ in kind says itself what its serving step indexes
    (``serving_params()``); a LlamaForCausalLM is read here. Scanned
    models (FLAGS_scan_layers: ``m.layers`` is an nn.LayerStack) unstack
    the leading axis back into the per-layer dicts the decode/prefill
    bodies index."""
    own = getattr(model, "serving_params", None)
    if own is not None:
        return own()
    from ..nn.scan_stack import LayerStack
    cfg = model.config
    m = model.model if hasattr(model, "model") else model
    layers = []
    if isinstance(m.layers, LayerStack):
        stacked = {k: m.layers.stacked_parameter(n)._data
                   for k, n in _STACKED_LAYER_KEYS.items()}
        for i in range(m.layers.num_layers):
            layers.append({k: v[i] for k, v in stacked.items()})
    else:
        def _resolve(layer, dotted):
            obj = layer
            for part in dotted.split("."):
                obj = getattr(obj, part)
            return obj

        for l in m.layers:
            layers.append({k: _resolve(l, n)._data
                           for k, n in _STACKED_LAYER_KEYS.items()})
    params = {
        "embed": m.embed_tokens.weight._data,
        "norm": m.norm.weight._data,
        "layers": layers,
    }
    if getattr(model, "lm_head", None) is not None:
        params["lm_head"] = model.lm_head.weight._data
    return params


def _block(pl, h, pos, cfg, kv=None, cache_layer=None, cur_len=None,
           paged=None):
    """One decoder layer. Returns (h, (k_full, v_full)).

    Training/prefill: kv is None, attends causally within h.
    Decode: cache_layer = (K, V) [b, max_len, Hkv, d]; h is [b, 1, H].
    Paged decode: ``paged=(page_size, interpret)`` and cache_layer =
    (Kp, Vp) [Hkv, b, pages_per_seq, page_size, d] — attention runs through
    the Pallas paged kernel (kernels/paged_attention.py), reading only the
    sequence's live pages (reference capability:
    block_multi_head_attention_kernel.cu).
    """
    H, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    b, s, _ = h.shape
    x = _rms_norm(h, pl["ln1"], cfg.rms_norm_eps)
    q = _wmat(x, pl["q"]).reshape(b, s, H, d)
    k = _wmat(x, pl["k"]).reshape(b, s, Hkv, d)
    v = _wmat(x, pl["v"]).reshape(b, s, Hkv, d)
    q = _rope(q, pos, cfg.rope_theta, d)
    k = _rope(k, pos, cfg.rope_theta, d)

    if cache_layer is None:
        # prefill: causal
        mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
        kr = _repeat_kv(k, H // Hkv)
        vr = _repeat_kv(v, H // Hkv)
        p = _attn_scores(q, kr, mask)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
        new_cache = (k, v)
    elif paged is not None:
        from ..kernels.paged_attention import paged_attention
        page_size, interpret = paged
        Kp, Vp = cache_layer               # [Hkv, b, pps, ps, d]
        pps = Kp.shape[2]
        p_idx = cur_len // page_size
        off = cur_len % page_size
        # write the new token into every sequence's current page (identity
        # block table: sequence i owns pool pages [i*pps, (i+1)*pps))
        kt = jnp.transpose(k, (2, 0, 1, 3))[:, :, None]   # [Hkv, b, 1, 1, d]
        vt = jnp.transpose(v, (2, 0, 1, 3))[:, :, None]
        Kp = jax.lax.dynamic_update_slice(Kp, kt, (0, 0, p_idx, off, 0))
        Vp = jax.lax.dynamic_update_slice(Vp, vt, (0, 0, p_idx, off, 0))
        tbl = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
        lens = jnp.full((b,), cur_len + 1, jnp.int32)
        o = paged_attention(q[:, 0],
                            Kp.reshape(Hkv, b * pps, page_size, d),
                            Vp.reshape(Hkv, b * pps, page_size, d),
                            tbl, lens, interpret=interpret)
        o = o[:, None]                      # [b, 1, H, d]
        new_cache = (Kp, Vp)
    else:
        K, V = cache_layer                       # [b, max_len, Hkv, d]
        K = jax.lax.dynamic_update_slice(K, k, (0, cur_len, 0, 0))
        V = jax.lax.dynamic_update_slice(V, v, (0, cur_len, 0, 0))
        # masked decode attention over the whole static cache
        valid = jnp.arange(K.shape[1])[None, None, None, :] <= cur_len
        kr = _repeat_kv(K, H // Hkv)
        vr = _repeat_kv(V, H // Hkv)
        p = _attn_scores(q, kr, valid)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
        new_cache = (K, V)

    h = h + _wmat(o.reshape(b, s, H * d), pl["o"])
    x = _rms_norm(h, pl["ln2"], cfg.rms_norm_eps)
    h = h + _wmat(jax.nn.silu(_wmat(x, pl["gate"])) * _wmat(x, pl["up"]),
                  pl["down"])
    return h, new_cache


def _logits(params, h, cfg):
    if "lm_head" in params:
        return h @ params["lm_head"]
    return h @ params["embed"].T


def _masked_logits(logits, temps, top_ks, top_ps):
    """The per-row sampling transform shared by every sampler in the
    repo (Generator's ``_sample``, the serving engine's ragged/burst
    steps, the speculative-decoding draft and verifier): scale by
    temperature, then mask to the top-k largest logits, then to the
    top-p nucleus. Rows with different knobs ride ONE jitted launch,
    which branches on the batch's knobs: the top-k sort runs only where
    some row has ``top_k > 0``, the nucleus sort, softmax and cumsum only
    where some row has ``top_p < 1``. A mask no row asks for is the
    identity on every row, so each row's result is bit-identical
    whichever branch the batch takes.

    logits [b, V]; temps [b] (> 0 — greedy rows are the caller's
    ``where``); top_ks [b] int32 (<= 0 disables; clamped to the vocab,
    so ``top_k >= V`` is a no-op instead of an out-of-range index at
    trace time); top_ps [b] f32 (>= 1.0 disables). Returns the
    masked/scaled logits [b, V] (disallowed entries at -1e30).
    """
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32) / temps[:, None]

    def top_k(logits):
        # keep the k largest (the kth value itself stays, ties keep)
        k_eff = jnp.clip(jnp.where(top_ks > 0, top_ks, V), 1, V)
        kth = jnp.take_along_axis(jnp.sort(logits, -1)[:, ::-1],
                                  (k_eff - 1)[:, None], -1)
        return jnp.where(logits < kth, -1e30, logits)

    def top_p(logits):
        # nucleus over the post-top-k logits (matches the legacy
        # sequential masking order bit for bit when both knobs are set)
        sorted_l = jnp.sort(logits, -1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, -1)
        cum = jnp.cumsum(probs, -1)
        cutoff_idx = jnp.sum(cum < top_ps[:, None], -1)      # [b]
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], -1)
        apply_p = (top_ps < 1.0)[:, None]
        return jnp.where(apply_p & (logits < cutoff), -1e30, logits)

    logits = jax.lax.cond(jnp.any(top_ks > 0), top_k, lambda x: x, logits)
    return jax.lax.cond(jnp.any(top_ps < 1.0), top_p, lambda x: x, logits)


def if_any_samples(temps, sampled, greedy, *operands):
    """The gate of every per-row sampling epilogue: ``sampled(*operands)``
    where some row aboard has ``temps > 0``, else ``greedy(*operands)``
    (an argmax: no sort, softmax or random draw). A ``lax.cond`` inside
    the one executable, on the rows' own knobs alone; pad rows carry
    ``temps`` 0 and do not flip it. ``sampled`` must give a greedy row
    what ``greedy`` gives it, so a row's result does not depend on what
    it is batched with. Keep the call outside any ``vmap`` that batches
    ``temps``: a batched predicate lowers to a select of both sides."""
    return jax.lax.cond(jnp.any(temps > 0), sampled, greedy, *operands)


def _greedy_probs(logits):
    return jax.nn.one_hot(jnp.argmax(logits, -1), logits.shape[-1],
                          dtype=jnp.float32)


def _sampled_probs(logits, temps, top_ks, top_ps):
    """:func:`sampling_probs` with no gate: for a batch known to hold a
    sampling row (greedy rows beside it still read their one-hot)."""
    safe_t = jnp.where(temps > 0, temps, 1.0)
    probs = jax.nn.softmax(_masked_logits(logits, safe_t, top_ks, top_ps),
                           -1)
    return jnp.where((temps > 0)[:, None], probs, _greedy_probs(logits))


def sampling_probs(logits, temps, top_ks, top_ps):
    """Per-row sampling DISTRIBUTION [b, V]: exactly the probabilities
    ``sample_rows`` draws from. Greedy rows (temp <= 0) are a one-hot at
    the argmax — which is what makes speculative decoding's rejection
    rule degenerate to argmax-equality on greedy rows, so spec-on greedy
    output is token-identical to spec-off (serving/spec_decode.py)."""
    logits = logits.astype(jnp.float32)
    return if_any_samples(
        temps, lambda lg: _sampled_probs(lg, temps, top_ks, top_ps),
        _greedy_probs, logits)


def request_keys(base_key, seeds, positions, tag):
    """Per-request, per-position PRNG streams for in-graph sampling:
    ``fold_in(fold_in(fold_in(base, seed), position), tag)`` per row.

    Every random draw a request consumes is a pure function of its own
    ``(seed, generation position, stream tag)`` — NOT of the engine-wide
    key sequence — so a request's sampled tokens are bit-identical
    regardless of what it is co-scheduled with, how its prompt was
    chunked, or whether it was preempted and recomputed (recompute
    replays the same positions). ``seeds``/``positions`` are [b] int32.
    """
    def one(s, g):
        k = jax.random.fold_in(base_key, s)
        k = jax.random.fold_in(k, g)
        return jax.random.fold_in(k, tag)
    return jax.vmap(one)(seeds, positions)


def sample_rows(logits, keys, temps, top_ks, top_ps):
    """Per-row sampling with per-row keys and knobs, in one launch that
    branches on the batch's knobs (:func:`if_any_samples`): greedy rows
    (temp <= 0) take argmax (the parity path), sampling rows draw
    categorically from their own masked logits under their own key. A
    row's token is bit-identical whatever it is batched with."""
    def greedy(logits):
        return jnp.argmax(logits, -1).astype(jnp.int32)

    def sampled(logits):
        safe_t = jnp.where(temps > 0, temps, 1.0)
        masked = _masked_logits(logits.astype(jnp.float32), safe_t, top_ks,
                                top_ps)
        drawn = jax.vmap(jax.random.categorical)(keys, masked)
        return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy(logits))

    return if_any_samples(temps, sampled, greedy, logits)


def _sample(logits, key, temperature, top_k, top_p):
    """logits [b, V] -> token ids [b] (scalar-knob wrapper over the
    per-row core; the Generator's host loop splits ``key`` itself).
    The knobs are Python scalars here, so knob-off paths specialize at
    trace time — plain temperature sampling pays no masking sorts."""
    if temperature == 0.0:
        return jnp.argmax(logits, -1)
    if (top_k is None or int(top_k) <= 0) and \
            (top_p is None or float(top_p) >= 1.0):
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / temperature, -1)
    b = logits.shape[0]
    temps = jnp.full((b,), float(temperature), jnp.float32)
    ks = jnp.full((b,), 0 if top_k is None else int(top_k), jnp.int32)
    ps = jnp.full((b,), 1.0 if top_p is None else float(top_p),
                  jnp.float32)
    return jax.random.categorical(
        key, _masked_logits(logits, temps, ks, ps), -1)


class Generator:
    """``Generator(model, max_len).generate(ids, max_new_tokens=...)``.

    ``quantized_mode="weight_only_int8"|"weight_only_int4"`` serves the
    model off a low-bit param pytree (quantization.quantize_params):
    projections stored int8 / packed int4 with per-out-channel scales,
    dequantized inside the jitted prefill/decode via the fused kernel.
    """

    def __init__(self, model, max_len=2048, paged=False, page_size=128,
                 quantized_mode=None, megakernel_scope=None,
                 prefill_megakernel=None):
        self.cfg = model.config
        self.params = extract_params(model)
        self.quantized_mode = quantized_mode
        if quantized_mode is not None:
            from ..quantization.low_bit import quantize_params
            self.params = quantize_params(self.params, quantized_mode)
        self.max_len = max_len
        cfg = self.cfg
        paged_opt = None
        if paged:
            if max_len % page_size != 0:
                raise ValueError(f"max_len {max_len} must be a multiple of "
                                 f"page_size {page_size}")
            from ..kernels import _on_tpu
            paged_opt = (page_size, not _on_tpu())   # interpret off-TPU
        self.paged = paged_opt
        scope = resolve_megakernel_scope(megakernel_scope)
        self.megakernel_scope = scope
        self.prefill_megakernel = resolve_prefill_megakernel(
            prefill_megakernel)
        prefill_fused = self.prefill_megakernel == "fused"
        # model scope scans _block over LayerStack-stacked [L, ...]
        # weights: the decode step (and the whole burst while_loop body)
        # lowers to ONE layer-body site instead of L. The stack is paid
        # once here; prefill keeps the per-layer list unless
        # FLAGS_prefill_megakernel lifts it too (the TTFT launch bound).
        from ..kernels.decode_megakernel import stack_layer_params
        if scope == "model":
            self._decode_params = dict(
                self.params, layers=stack_layer_params(
                    self.params["layers"]))
        else:
            self._decode_params = self.params
        if not prefill_fused:
            self._prefill_params = self.params
        elif scope == "model":
            self._prefill_params = self._decode_params
        else:
            self._prefill_params = dict(
                self.params, layers=stack_layer_params(
                    self.params["layers"]))

        def cache_of(b, k, v, dtype):
            # write prompt K/V into the static cache
            K = jnp.zeros((b, max_len, cfg.num_key_value_heads,
                           cfg.head_dim), dtype)
            V = jnp.zeros_like(K)
            K = jax.lax.dynamic_update_slice(K, k, (0, 0, 0, 0))
            V = jax.lax.dynamic_update_slice(V, v, (0, 0, 0, 0))
            if paged_opt is not None:
                pps = max_len // page_size
                hkv, d = cfg.num_key_value_heads, cfg.head_dim
                # [b, max_len, Hkv, d] -> [Hkv, b, pps, ps, d]
                K = jnp.transpose(
                    K.reshape(b, pps, page_size, hkv, d), (3, 0, 1, 2, 4))
                V = jnp.transpose(
                    V.reshape(b, pps, page_size, hkv, d), (3, 0, 1, 2, 4))
            return K, V

        @jax.jit
        def prefill(params, ids):
            b, s = ids.shape
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            h = params["embed"][ids]
            if prefill_fused:
                # scan-over-layers prefill: the whole prompt pass — the
                # causal layer body AND its cache write — lowers to ONE
                # layer-body site, so a prefill costs O(1) launches at
                # any depth; caches come out stacked [L, ...] (the
                # model-scope decode layout)
                def layer_body(hc, lyr):
                    hc, (k, v) = _block(lyr, hc, pos, cfg)
                    return hc, cache_of(b, k, v, hc.dtype)
                h, caches = jax.lax.scan(layer_body, h, params["layers"])
            else:
                caches = []
                for lyr in params["layers"]:
                    h, (k, v) = _block(lyr, h, pos, cfg)
                    caches.append(cache_of(b, k, v, h.dtype))
            h = _rms_norm(h, params["norm"], cfg.rms_norm_eps)
            return _logits(params, h[:, -1], cfg), caches

        def _decode_core(params, caches, token, cur_len, key, temperature,
                         top_k, top_p):
            b = token.shape[0]
            pos = jnp.full((b, 1), cur_len, jnp.int32)
            h = params["embed"][token[:, None]]
            if scope == "model":
                # scan-over-layers: caches arrive stacked [L, ...] (see
                # generate()), params["layers"] is the stacked tree —
                # one layer-body site in the lowered program
                def layer_body(hc, xs):
                    pl, cl = xs
                    hc, cl2 = _block(pl, hc, pos, cfg, cache_layer=cl,
                                     cur_len=cur_len, paged=paged_opt)
                    return hc, cl2
                h, new_caches = jax.lax.scan(layer_body, h,
                                             (params["layers"], caches))
            else:
                new_caches = []
                for pl, cl in zip(params["layers"], caches):
                    h, cl2 = _block(pl, h, pos, cfg, cache_layer=cl,
                                    cur_len=cur_len, paged=paged_opt)
                    new_caches.append(cl2)
            h = _rms_norm(h, params["norm"], cfg.rms_norm_eps)
            logits = _logits(params, h[:, 0], cfg)
            nxt = _sample(logits, key, temperature, top_k, top_p)
            return nxt, new_caches

        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnums=(5, 6, 7))
        def decode_step(params, caches, token, cur_len, key, temperature,
                        top_k, top_p):
            return _decode_core(params, caches, token, cur_len, key,
                                temperature, top_k, top_p)

        @functools.partial(jax.jit, donate_argnums=(1,),
                           static_argnums=(7, 8, 9, 10, 11))
        def decode_burst(params, caches, token, start_len, key, finished,
                         n_steps, temperature, top_k, top_p, eos_token_id,
                         burst_cap):
            # the on-device token loop: up to burst_cap decode iterations
            # (sample -> cache append -> EOS gate) inside ONE executable;
            # n_steps (traced) bounds the trip count so every burst size
            # reuses the same compilation. The per-step key split mirrors
            # the host loop exactly, so sampling draws are identical too.
            b = token.shape[0]
            out0 = jnp.zeros((b, burst_cap), token.dtype)

            def cond(c):
                i, _, _, _, finished, _ = c
                go = i < n_steps
                if eos_token_id is not None:
                    # do-while: the per-token loop breaks AFTER its
                    # append, so a burst entered with every row already
                    # finished (prefill sampled eos) still appends
                    # exactly one eos pad before stopping
                    go = go & ((i == 0) | ~jnp.all(finished))
                return go

            def body(c):
                i, token, caches, key, finished, out = c
                key, sub = jax.random.split(key)
                nxt, caches = _decode_core(params, caches, token,
                                           start_len + i, sub,
                                           temperature, top_k, top_p)
                if eos_token_id is not None:
                    # rows already finished emit eos forever (pad), same
                    # as the host loop's post-eos masking
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                out = out.at[:, i].set(nxt)
                return (i + 1, nxt, caches, key, finished, out)

            i, token, caches, key, finished, out = jax.lax.while_loop(
                cond, body,
                (jnp.asarray(0, jnp.int32), token, caches, key, finished,
                 out0))
            return token, caches, key, finished, out, i

        self._prefill = prefill
        self._decode = decode_step
        self._decode_burst = decode_burst

    def prefill_lowering(self, batch=1, prompt_len=8):
        """StableHLO text of the prefill executable for a given prompt
        shape — the launch-forensics surface for
        ``jit.hlo_forensics.launch_stats`` (fused prefill collapses the
        per-layer marker sites to one)."""
        ids = jnp.zeros((batch, prompt_len), jnp.int32)
        return self._prefill.lower(self._prefill_params, ids).as_text()

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=None, top_p=None, eos_token_id=None, seed=0,
                 burst_tokens=None):
        """``burst_tokens`` > 1 moves the token loop on-device: the host
        dispatches one jitted ``lax.while_loop`` burst of up to that
        many decode iterations instead of one executable per token
        (default: ``FLAGS_decode_burst_tokens``; 1 keeps the per-token
        path, bit-identical to the pre-burst engine)."""
        if burst_tokens is None:
            burst_tokens = int(GLOBAL_FLAGS.get("decode_burst_tokens"))
        if burst_tokens < 1:
            raise ValueError(f"burst_tokens must be >= 1, got "
                             f"{burst_tokens}")
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(np.asarray(input_ids))
        if ids.ndim == 1:
            ids = ids[None]
        b, s = ids.shape
        if s + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {s} + new {max_new_tokens} exceeds max_len "
                f"{self.max_len}")
        key = jax.random.key(seed)
        _HOST_DISPATCH["count"] += 1
        logits, caches = self._prefill(self._prefill_params, ids)
        if self.prefill_megakernel == "fused":
            # scan prefill already emits stacked [L, ...] caches — the
            # model-scope decode layout; layer scope wants the list back
            if self.megakernel_scope != "model":
                L = len(self.params["layers"])
                caches = [jax.tree.map(lambda x, i=i: x[i], caches)
                          for i in range(L)]
        elif self.megakernel_scope == "model":
            # one host-side stack after prefill; the stacked pytree then
            # round-trips through decode_step/decode_burst (donated)
            # without ever unstacking — the scan indexes it in-place
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
        key, sub = jax.random.split(key)
        token = _sample(logits, sub, temperature, top_k, top_p)
        finished = np.zeros((b,), bool)
        if eos_token_id is not None:
            finished |= np.asarray(token) == eos_token_id
        out = [token]
        if burst_tokens > 1:
            fin = jnp.asarray(finished)
            done = 1
            first = True
            while done < max_new_tokens:
                # the per-token loop always runs its first decode
                # iteration (the finished.all() break sits after the
                # append), so only later bursts early-out on finished
                if not first and eos_token_id is not None \
                        and bool(np.asarray(fin).all()):
                    break
                first = False
                n = min(burst_tokens, max_new_tokens - done)
                _HOST_DISPATCH["count"] += 1
                token, caches, key, fin, buf, cnt = self._decode_burst(
                    self._decode_params, caches, token, s + done - 1,
                    key, fin, n, temperature, top_k, top_p, eos_token_id,
                    burst_tokens)
                cnt = int(cnt)
                if cnt == 0:
                    break
                for j in range(cnt):
                    out.append(buf[:, j])
                done += cnt
            finished = np.asarray(fin)
        else:
            for i in range(max_new_tokens - 1):
                key, sub = jax.random.split(key)
                _HOST_DISPATCH["count"] += 1
                token, caches = self._decode(self._decode_params, caches,
                                             token, s + i, sub,
                                             temperature, top_k, top_p)
                if eos_token_id is not None:
                    # rows already finished emit eos forever (pad),
                    # regardless of what the model sampled from post-eos
                    # context
                    token = jnp.where(jnp.asarray(finished), eos_token_id,
                                      token)
                    finished |= np.asarray(token) == eos_token_id
                out.append(token)
                if eos_token_id is not None and finished.all():
                    break
        gen = jnp.stack(out, 1)
        return Tensor(jnp.concatenate([ids, gen], 1))


def generate(model, input_ids, max_len=512, **kwargs):
    """One-shot convenience: build a Generator and sample."""
    return Generator(model, max_len=max_len).generate(input_ids, **kwargs)


__all__ = ["Generator", "generate", "extract_params",
           "host_dispatch_count", "if_any_samples", "request_keys",
           "resolve_megakernel_scope", "sample_rows", "sampling_probs"]
