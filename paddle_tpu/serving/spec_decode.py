"""Speculative decoding: int4 draft proposals, one-pass ragged verification.

The serving engine's decode cost is one full target-model launch per
generated token. This module cuts that to less than one: a small DRAFT
model (served off the existing ``quantize_params(mode="weight_only_int4")``
low-bit path) proposes ``k`` tokens per scheduled decode row, and the
target model verifies all ``k+1`` positions in ONE ragged step —
verification rows are just prefill-shaped chunks (``q_len = k + 1``) in
the engine's existing fixed-shape executable, so the serving trace-count
gate stays at 1 and a fully-accepted round commits ``k+1`` tokens for a
single target launch.

Acceptance is standard rejection sampling (Leviathan et al. /
speculative sampling): candidate ``d_i`` drawn from the draft
distribution ``q_i`` is accepted with probability
``min(1, p_{i-1}(d_i) / q_i(d_i))`` against the target distribution
``p_{i-1}`` at the same position; the first rejection resamples from the
normalized residual ``max(p - q, 0)`` and a fully-accepted round samples
one bonus token from ``p_k``. The induced output distribution is EXACTLY
the target-only sampling distribution (tests/test_spec_decode.py proves
the identity numerically on a small vocab), and because greedy rows'
"distributions" are one-hot argmaxes (models/generation.sampling_probs),
the rule degenerates to argmax-equality on greedy rows — spec-on greedy
output is token-identical to spec-off and to sequential
``Generator.generate``.

Randomness: every draw is a per-request stream —
``fold_in(fold_in(fold_in(base, request_seed), generation_position),
tag)`` with distinct tags for draft sampling, acceptance uniforms, and
the residual/bonus draw — so a request's sampled tokens are
bit-reproducible regardless of batch composition, chunk boundaries, or
preemption-recompute (models/generation.request_keys).

KV bookkeeping: the target step appends K/V for all ``k+1`` verified
positions before attention (it must — attention reads them); when only
``j <= k`` candidates survive, the engine ROLLS BACK the pool's
committed length (``PagedKVPool.rollback``) without freeing pages — the
rejected tail slots are garbage the next append overwrites, and
attention never reads past the committed length. The draft runs the
same protocol against its own small paged pool (same ``PagedKVPool``
block-table machinery, fp pages).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..core.flags import define_flag
from ..models.generation import (LayerKind, _logits, _rms_norm, _rope,
                                 _sampled_probs, _wmat, extract_params,
                                 if_any_samples, request_keys, sample_rows,
                                 sampling_probs)
from ..kernels.paged_attention import (kv_append, ragged_latent_attention,
                                       ragged_paged_attention)
from ..profiler import phases
from .kv_cache import NULL_PAGE, PagedKVPool, PoolExhausted


def _check_spec_tokens(v):
    if int(v) < 0:
        raise ValueError(
            f"FLAGS_spec_decode_tokens must be >= 0, got {v!r}")


define_flag("spec_decode_tokens", int, 0,
            "speculative-decoding draft length k: how many tokens the "
            "draft model proposes per scheduled decode row, verified by "
            "the target in ONE ragged step (q_len = k+1 per row). 0 (the "
            "default) disables speculation; takes effect only on an "
            "LLMEngine constructed with draft_model=...",
            on_set=_check_spec_tokens)

#: stream tags for the per-request PRNG streams (request_keys): the
#: draft's proposal draw, the verifier's acceptance uniform, and the
#: residual/bonus/plain-sampling draw all at one generation position
#: must be independent
DRAFT_TAG, ACCEPT_TAG, FINAL_TAG = 0, 1, 2


def _ragged_packing(q_starts, q_lens, T):
    """Row/liveness masks of a packed query buffer: ``tok_row[t]`` is
    the row slot token ``t`` belongs to, ``live[t]`` whether it sits
    inside that row's ``q_len`` (slot padding and pad rows are dead)."""
    tok_row = (jnp.searchsorted(q_starts, jnp.arange(T, dtype=jnp.int32),
                                side="right") - 1)
    tok_row = jnp.maximum(tok_row, 0)
    live = (jnp.arange(T) - q_starts[tok_row]) < q_lens[tok_row]
    return tok_row, live


class StepOperands:
    """The ragged step's small operands as ONE int32 buffer, and its
    small results as one int32 array: what a launch moves between host
    and device is one put and one read-back.

    Laid out once at engine build from the step's fixed shapes (``T``
    packed tokens, ``R`` row slots, ``PPS``-wide tables, ``K`` draft
    tokens) and from which optional operands exist (``window``: the
    window page group's tables ``tbls_w``; ``adapters``: the per-token
    LoRA ``slot_ids``; ``slots``: each row's state slot, for linear
    layers). Every operand is a named static slice; the float
    ones travel as their own bits (an ``np.float32`` view on the host,
    ``bitcast_convert_type`` in the graph). ``host()`` and ``unpack()``
    are the two readings of the same slices."""

    def __init__(self, T, R, PPS, K, *, window=False, adapters=False,
                 slots=False):
        i32, f32 = np.int32, np.float32
        # name, shape, dtype, what a pad row reads
        fields = [("tokens", (T,), i32, 0), ("positions", (T,), i32, 0),
                  ("tbls", (R, PPS), i32, NULL_PAGE),
                  ("q_starts", (R,), i32, T),     # pad rows: start past T
                  ("q_lens", (R,), i32, 0), ("kv_lens", (R,), i32, 0),
                  ("sample_idx", (R, K + 1), i32, 0),
                  ("temps", (R,), f32, 0.0), ("top_ks", (R,), i32, 0),
                  ("top_ps", (R,), f32, 1.0), ("seeds", (R,), i32, 0),
                  ("sample_pos", (R,), i32, 0), ("spec_lens", (R,), i32, 0)]
        if window:
            fields.append(("tbls_w", (R, PPS), i32, NULL_PAGE))
        if adapters:
            fields.append(("slot_ids", (T,), i32, 0))
        if slots:
            fields.append(("slots", (R,), i32, 0))     # pad rows: null slot
        self.R, self.K = R, K
        self.fields, lo = {}, 0
        for name, shape, dtype, _ in fields:
            n = int(np.prod(shape))
            self.fields[name] = (lo, lo + n, shape, dtype)
            lo += n
        self.size = lo
        self._blank = np.zeros((lo,), i32)
        for (*_, fill), view in zip(fields, self._views(self._blank)):
            view[...] = fill

    def _views(self, buf):
        return [buf[lo:hi].view(dtype).reshape(shape)
                for lo, hi, shape, dtype in self.fields.values()]

    def host(self):
        """A fresh buffer of pad rows and a dict of numpy views into it,
        one an operand: rows written through a view land in the buffer."""
        buf = self._blank.copy()
        return buf, dict(zip(self.fields, self._views(buf)))

    def unpack(self, buf):
        """In the graph: the operands out of the device's copy of the
        buffer, by static slices."""
        ops = {}
        for name, (lo, hi, shape, dtype) in self.fields.items():
            x = buf[lo:hi].reshape(shape)
            if dtype is np.float32:
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            ops[name] = x
        return ops

    def pack_results(self, out, n_out, finite):
        """In the graph: ``out`` ``[R, K+1]``, ``n_out`` (``[R]``, the
        routed layers' three counts behind it where there are any) and
        ``finite`` ``[R]`` as one int32 array."""
        return jnp.concatenate([out.reshape(-1), n_out,
                                finite.astype(jnp.int32)])

    def read_results(self, back):
        """On the host: ``(out, n_out, finite)`` out of the one array."""
        R, n = self.R, self.R * (self.K + 1)
        return back[:n].reshape(R, self.K + 1), back[n:-R], back[-R:] != 0


def _ragged_fp_layer(lyr, h, Kp, Vp, positions, tbls, tok_row, live,
                     q_starts, q_lens, kv_lens, cfg, page_size, max_pages,
                     q_block, interpret, *, adapters=None, slots=None,
                     kind=LayerKind(), moe_stats=None, state_slots=None):
    """One fp decoder layer of the ragged forward: qkv proj -> rope ->
    page append -> ragged attention -> o proj -> mlp. Returns
    ``(h, Kp, Vp)``.

    ``kind`` (``models.generation.LayerKind``, static) says what the
    layer is where it is not a Llama layer: RMSNorm over each q and k
    head, rotary or none, a window handed to the kernel (``tbls`` and
    the pools are then its page group's), a dense or a routed
    feed-forward (``nn/moe_dropless.py``; a routed layer appends its
    ``[3]`` counts to the list ``moe_stats``), or latent attention
    (:func:`_latent_attention`: ``Kp`` is then the layer's ONE pool
    array, ``Vp`` None, and None comes back in its place), an output
    gate and rotary on part of each head, or linear attention
    (:func:`_linear_attention`: ``Kp``, ``Vp`` are then the layer's
    state and convolution slots, ``state_slots [R]`` each row's slot).
    Layers of every kind trace into the one executable.

    This is THE fp layer body — the engine's ragged step (fp pools) and
    the draft worker's forward both call it, so draft/target numerics
    cannot drift (a silent divergence here would collapse speculative
    acceptance with nothing pointing at the cause). The engine's int8
    pool branch stays in engine.py: its append/attention contract
    (running-amax requant, scale-aware gather) is different machinery,
    not a copy of this.

    ``adapters``/``slots`` (multi-tenant LoRA, paddle_tpu.tenancy):
    this layer's ``{proj: (A [S, r, d_in], B [S, d_out, r])}`` slab and
    the per-token slot vector ``[T]`` — each projection then adds the
    batched per-request delta (slot 0 is the all-zero base-model slot).
    None (the default) adds NO operands, so adapter-free engines lower
    byte-identical HLO."""
    ps = page_size
    T = h.shape[1]

    def lo(p):
        if adapters is None:
            return None
        A, B = adapters[p]
        return (A, B, slots)

    def feed_forward(h):
        with phases.phase("norm"):
            x = _rms_norm(h, lyr["ln2"], cfg.rms_norm_eps)
        if kind.mlp == "sparse":
            y = _routed_mlp(lyr, x, live, cfg, interpret, moe_stats)
            with phases.phase("moe.combine"):       # the residual add
                return h + y
        with phases.phase("mlp"):
            return h + _wmat(
                jax.nn.silu(_wmat(x, lyr["gate"], lora=lo("gate")))
                * _wmat(x, lyr["up"], lora=lo("up")),
                lyr["down"], lora=lo("down"))

    if kind.linear:
        h, Kp, Vp = _linear_attention(lyr, h, Kp, Vp, state_slots, tok_row,
                                      live, q_starts, q_lens, kv_lens, cfg,
                                      q_block, interpret)
        return feed_forward(h), Kp, Vp
    with phases.phase("attn.core"):
        slot = _page_slots(positions, tbls, tok_row, live, ps, max_pages)
    if kind.latent:
        h, Kp = _latent_attention(lyr, h, Kp, positions, slot, tbls,
                                  q_starts, q_lens, kv_lens, cfg, q_block,
                                  interpret)
        return feed_forward(h), Kp, None
    H, Hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)

    with phases.phase("norm"):
        x = _rms_norm(h, lyr["ln1"], cfg.rms_norm_eps)
    with phases.phase("attn.qkv"):
        if kind.gate:
            # each head's query and gate side by side
            q = _wmat(x, lyr["q"]).reshape(1, T, H, 2 * d)
            q, gate = q[..., :d], q[..., d:]
        else:
            q = _wmat(x, lyr["q"], lora=lo("q")).reshape(1, T, H, d)
        k = _wmat(x, lyr["k"], lora=lo("k")).reshape(1, T, Hkv, d)
        v = _wmat(x, lyr["v"], lora=lo("v")).reshape(1, T, Hkv, d)
        if kind.qk_norm:
            q = _rms_norm(q, lyr["q_norm"], cfg.rms_norm_eps)
            k = _rms_norm(k, lyr["k_norm"], cfg.rms_norm_eps)
        if kind.rope and kind.rotary_dim is not None:
            q, k = (_rope_part(x, positions, cfg.rope_theta, kind.rotary_dim)
                    for x in (q, k))
        elif kind.rope:
            q = _rope(q, positions[None], cfg.rope_theta, d)
            k = _rope(k, positions[None], cfg.rope_theta, d)
    with phases.phase("attn.core"):
        kt = jnp.transpose(k[0], (1, 0, 2))              # [Hkv, T, d]
        vt = jnp.transpose(v[0], (1, 0, 2))
        Kp = kv_append(Kp, slot, kt, interpret=interpret)
        Vp = kv_append(Vp, slot, vt, interpret=interpret)
        o = ragged_paged_attention(q[0], Kp, Vp, tbls, q_starts, q_lens,
                                   kv_lens, q_block=q_block,
                                   interpret=interpret, window=kind.window)
    with phases.phase("attn.out"):
        if kind.gate:
            o = (o.astype(jnp.float32)
                 * jax.nn.sigmoid(gate[0].astype(jnp.float32))).astype(h.dtype)
        h = h + _wmat(o.reshape(1, T, H * d), lyr["o"], lora=lo("o"))
    return feed_forward(h), Kp, Vp


def _rope_part(x, positions, theta, rotary_dim):
    """Rotary on the first ``rotary_dim`` dims of each head of ``x [1, T,
    heads, d]``, the rest as they are."""
    rot = _rope(x[..., :rotary_dim], positions[None], theta, rotary_dim)
    return jnp.concatenate([rot, x[..., rotary_dim:]], -1)


def _linear_attention(lyr, h, S, Cv, state_slots, tok_row, live, q_starts,
                      q_lens, kv_lens, cfg, q_block, interpret):
    """The mixer of a LINEAR (Gated DeltaNet) layer, ``models/qwen3_next.py``
    writing its equations down: the in-projections in the published
    column layout (a key head's q, k, its value heads' v and z; its value
    heads' b and a), the causal depthwise convolution over q, k and v
    (``taps`` wide, the row's last ``taps - 1`` inputs before this step
    kept in its slot ``Cv``), SiLU, L2-normalised q and k, ``beta =
    sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the
    recurrence over each row from its state slot ``S``
    (``kernels/gated_delta.py``); the gated norm ``w RMSNorm(o)
    silu(z)`` a head; ``out_proj``. A row whose chunk starts at position
    0 starts from a zero state and zero inputs before it. Returns ``(h,
    S, Cv)``."""
    from ..kernels.gated_delta import ragged_gated_delta
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rep, T = hv // hk, h.shape[1]
    taps = Cv.shape[1] + 1
    f32 = jnp.float32
    with phases.phase("norm"):
        x = _rms_norm(h, lyr["ln1"], cfg.rms_norm_eps)[0]
    with phases.phase("linear.proj"):
        qkvz = _wmat(x, lyr["in_qkvz"]).reshape(T, hk, 2 * dk + 2 * rep * dv)
        ba = _wmat(x, lyr["in_ba"]).reshape(T, hk, 2 * rep)
        z = qkvz[..., 2 * dk + rep * dv:].reshape(T, hv, dv)
        mixed = jnp.concatenate(
            [qkvz[..., :dk].reshape(T, -1), qkvz[..., dk:2 * dk].reshape(T, -1),
             qkvz[..., 2 * dk:2 * dk + rep * dv].reshape(T, -1)], -1)
        fresh = (kv_lens - q_lens) == 0                   # [R]
        tail = jnp.where(fresh[:, None, None], 0, Cv[state_slots])
        # a token's inputs j back: this step's where the row has them,
        # the slot's tail for its first j tokens
        off = jnp.arange(T) - q_starts[tok_row]
        w = lyr["conv"].astype(f32)                       # [taps, channels]
        acc = mixed.astype(f32) * w[taps - 1]
        for j in range(1, taps):
            back = jnp.where((off >= j)[:, None],
                             jnp.pad(mixed, ((j, 0), (0, 0)))[:T], 0)
            first = q_starts[:, None] + jnp.arange(j)     # [R, j]
            back = back.at[first].set(tail[:, taps - 1 - j:taps - 1],
                                      mode="drop")
            acc = acc + back.astype(f32) * w[taps - 1 - j]
        conv = jax.nn.silu(acc)
        # the slot keeps the row's last taps - 1 inputs for its next step
        last = q_lens[:, None] - (taps - 1) + jnp.arange(taps - 1)
        keep = jnp.where(
            (last >= 0)[..., None],
            mixed[jnp.clip(q_starts[:, None] + last, 0, T - 1)],
            tail[jnp.arange(tail.shape[0])[:, None],
                 jnp.clip(last + taps - 1, 0, taps - 2)])
        Cv = Cv.at[state_slots].set(keep.astype(Cv.dtype))

        def unit(y):
            return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
        q = unit(conv[:, :hk * dk].reshape(T, hk, dk)) * dk ** -0.5
        k = unit(conv[:, hk * dk:2 * hk * dk].reshape(T, hk, dk))
        v = conv[:, 2 * hk * dk:].reshape(T, hv, dv)
        beta = jax.nn.sigmoid(ba[..., :rep].reshape(T, hv).astype(f32))
        g = -jnp.exp(lyr["A_log"].astype(f32)) * jax.nn.softplus(
            ba[..., rep:].reshape(T, hv).astype(f32)
            + lyr["dt_bias"].astype(f32))
    with phases.phase("linear.scan"):
        o, S = ragged_gated_delta(q, k, v, g, beta, S, state_slots, q_starts,
                                  q_lens, fresh, q_block=q_block,
                                  interpret=interpret)
    with phases.phase("linear.out"):
        o = jnp.where(live[:, None, None], o, 0.0)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_norm_eps)
        o = o * lyr["out_norm"].astype(f32) * jax.nn.silu(z.astype(f32))
        h = h + _wmat(o.astype(h.dtype).reshape(1, T, hv * dv),
                      lyr["out_proj"])
    return h, S, Cv


def _page_slots(positions, tbls, tok_row, live, page_size, max_pages):
    """The pool row each packed token's cache entry goes to: every live
    token's page slot; dead tokens (slot padding / pad rows) name the
    null page, which the append skips."""
    page_idx = jnp.clip(positions // page_size, 0, max_pages - 1)
    page = jnp.where(live, tbls[tok_row, page_idx], NULL_PAGE)
    return page * page_size + positions % page_size


def _latent_attention(lyr, h, Cp, positions, slot, tbls, q_starts, q_lens,
                      kv_lens, cfg, q_block, interpret):
    """The attention half of a LATENT layer (``models/deepseek_mla.py``
    writes the equations down), absorbed: queries through their low
    rank and its norm; the token's compressed row ``[RMSNorm(c_kv) ;
    rope(k_r)]`` appended ONCE to the layer's one pool array ``Cp
    [pages, page_size, row]``; each head's ``q_nope`` carried into the
    row's coordinates by ``w_uk``; ``ragged_latent_attention`` over the
    rows; the weighted rows carried back out by ``w_uv``; ``o``.
    ``slot [T]``: :func:`_page_slots`. Returns ``(h, Cp)``."""
    H, r = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    T, W = h.shape[1], Cp.shape[-1]
    inv_freq = jnp.asarray(cfg.rope_inv_freq())
    with phases.phase("norm"):
        x = _rms_norm(h, lyr["ln1"], cfg.rms_norm_eps)
    with phases.phase("attn.qkv"):
        c_q = _rms_norm(_wmat(x, lyr["q_a"]), lyr["q_a_norm"],
                        cfg.rms_norm_eps)
        q = _wmat(c_q, lyr["q_b"]).reshape(1, T, H, nope + rope)
        q_rope = _rope(q[..., nope:], positions[None], None, rope, inv_freq)
        ckv = _wmat(x, lyr["kv_a"])                      # [1, T, r + rope]
        c_kv = _rms_norm(ckv[..., :r], lyr["kv_a_norm"], cfg.rms_norm_eps)
        k_r = _rope(ckv[..., None, r:], positions[None], None, rope,
                    inv_freq)[:, :, 0]
        pad = W - r - rope                               # the row's padding
        row = jnp.concatenate(
            [c_kv[0], k_r[0], jnp.zeros((T, pad), c_kv.dtype)], -1)
    with phases.phase("attn.core"):
        # the append kernel's pages carry a kv-head axis: one head here
        Cp = kv_append(Cp[None], slot, row[None], interpret=interpret)[0]
    with phases.phase("attn.qkv"):
        # absorb: q_nope . k_nope_j = (q_nope w_uk) . c_kv_j
        q_lat = jnp.einsum("thn,hnr->thr", q[0, ..., :nope], lyr["w_uk"])
        q_row = jnp.concatenate(
            [q_lat, q_rope[0].astype(q_lat.dtype),
             jnp.zeros((T, H, pad), q_lat.dtype)], -1)   # [T, H, W]
    with phases.phase("attn.core"):
        o = ragged_latent_attention(
            q_row, Cp, tbls, q_starts, q_lens, kv_lens, v_width=r,
            scale=cfg.softmax_scale, q_block=q_block, interpret=interpret)
    with phases.phase("attn.out"):
        # un-absorb: o = (sum_j p_j c_kv_j) w_uv
        o = jnp.einsum("thr,hrv->thv", o, lyr["w_uv"])
        return h + _wmat(o.reshape(1, T, -1), lyr["o"]), Cp


def _routed_mlp(lyr, x, live, cfg, interpret, moe_stats):
    """The routed feed-forward of a ``sparse`` layer over the packed
    step: every live token's picks among ALL the router's experts, the
    part of their sum whose experts this model holds, and the shared
    expert. ``x [1, T, hidden]``."""
    from ..nn.moe_dropless import (dropless_experts, route_sigmoid,
                                   route_softmax, shared_expert_gate)
    if getattr(cfg, "router_scoring", "sigmoid") == "softmax":
        idx, gates = route_softmax(x[0], lyr["router"],
                                   top_k=cfg.num_experts_per_tok,
                                   norm_topk_prob=cfg.norm_topk_prob)
    else:
        idx, gates = route_sigmoid(
            x[0], lyr["router"], lyr["router_bias"],
            top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob,
            n_group=getattr(cfg, "n_group", 1),
            topk_group=getattr(cfg, "topk_group", 1))
    y, stats = dropless_experts(
        x[0], idx, gates, live, lyr["experts_gate"], lyr["experts_up"],
        lyr["experts_down"], first=cfg.expert_offset, interpret=interpret)
    if moe_stats is not None:
        moe_stats.append(stats)
    with phases.phase("mlp"):
        shared = _wmat(jax.nn.silu(_wmat(x, lyr["shared_gate"]))
                       * _wmat(x, lyr["shared_up"]), lyr["shared_down"])
        if "shared_expert_gate" in lyr:
            shared = (shared_expert_gate(x[0], lyr["shared_expert_gate"])
                      * shared.astype(jnp.float32)).astype(shared.dtype)
    with phases.phase("moe.combine"):
        return y[None] + shared


@phases.scoped("sample")
def speculative_sample(target_logits, draft_tokens, draft_probs, spec_lens,
                       temps, top_ks, top_ps, base_key, seeds, sample_pos):
    """The in-graph rejection sampler: target logits at ``k+1`` verify
    positions per row -> committed tokens.

    target_logits [R, K+1, V]; draft_tokens [R, K]; draft_probs
    [R, K, V] (the EXACT per-position distributions the draft sampled
    from); spec_lens [R] in [0, K] (0 = plain row: no candidates, the
    output is one direct sample from ``p_0`` — exactly the non-spec
    engine's sampling path); temps/top_ks/top_ps [R] per-row knobs;
    seeds/sample_pos [R] per-request stream state (sample_pos = the
    generation index of the row's FIRST committed token this round).

    Returns ``(out_tokens [R, K+1], n_out [R])``: ``out_tokens[r, :j]``
    are the accepted draft candidates (``j = n_out - 1``) and
    ``out_tokens[r, j]`` is the residual resample (on rejection) or the
    bonus/plain sample — ``n_out`` tokens commit, in order.

    The step does only what the rows aboard ask for
    (``generation.if_any_samples``): where none samples, the tokens are
    argmaxes and no distribution, sort or random draw is computed; where
    one does, every row runs the sampler, a greedy row's one-hot giving
    it the same argmax. A row's tokens do not depend on the branch.
    """
    R, K1, _V = target_logits.shape
    K = K1 - 1
    rows = jnp.arange(R)
    cand = jnp.arange(K)[None, :] < spec_lens[:, None]

    def leading(accept):
        # leading-accept run length: candidates commit strictly in order
        return jnp.sum(jnp.cumprod((accept & cand).astype(jnp.int32), -1),
                       -1)

    def greedy(target_logits):
        # no row aboard samples: the rule above on one-hot distributions,
        # taken directly. A candidate is accepted iff it is the target's
        # argmax, and the residual / bonus / plain token is the argmax.
        top = jnp.argmax(target_logits, -1).astype(jnp.int32)  # [R, K+1]
        n_acc = leading(draft_tokens == top[:, :K])
        return n_acc, top[rows, n_acc]

    def sampled(target_logits):
        # per-position target sampling distributions (greedy rows:
        # one-hot); the knobs are closed over, so the masks' own gates
        # (generation._masked_logits) stay conds under this vmap
        p = jax.vmap(lambda lg: _sampled_probs(lg, temps, top_ks, top_ps),
                     in_axes=1, out_axes=1)(
                         target_logits.astype(jnp.float32))  # [R, K+1, V]
        if K > 0:
            p_at = jnp.take_along_axis(p[:, :K], draft_tokens[..., None],
                                       -1)[..., 0]             # [R, K]
            q_at = jnp.take_along_axis(draft_probs,
                                       draft_tokens[..., None], -1)[..., 0]
            ratio = p_at / jnp.maximum(q_at, 1e-30)
            # acceptance uniforms off the SAME stream derivation every
            # sampler in the repo uses (request_keys) — one definition
            u = jax.vmap(
                lambda i: jax.vmap(jax.random.uniform)(
                    request_keys(base_key, seeds, sample_pos + i,
                                 ACCEPT_TAG)),
                out_axes=1)(jnp.arange(K))                     # [R, K]
            n_acc = leading(u < ratio)
        else:
            n_acc = jnp.zeros((R,), jnp.int32)
        p_fin = p[rows, n_acc]                                 # [R, V]
        if K > 0:
            # first-rejection residual: max(p - q, 0) renormalized — the
            # distribution that makes the committed token EXACTLY target-
            # distributed. A zero residual (p == q) can only coincide with
            # acceptance, so the p_fin fallback is never actually drawn.
            rejected = n_acc < spec_lens
            q_fin = draft_probs[rows, jnp.minimum(n_acc, K - 1)]
            res = jnp.maximum(p_fin - q_fin, 0.0)
            rs = jnp.sum(res, -1, keepdims=True)
            res = jnp.where(rs > 0, res / jnp.maximum(rs, 1e-30), p_fin)
            dist = jnp.where(rejected[:, None], res, p_fin)
        else:
            dist = p_fin
        fkeys = request_keys(base_key, seeds, sample_pos + n_acc, FINAL_TAG)
        y = jax.vmap(jax.random.categorical)(fkeys, jnp.log(dist)) \
            .astype(jnp.int32)
        return n_acc, y

    # the gate sits outside the vmap over the K + 1 positions: one
    # predicate a step, on the rows' knobs alone
    n_acc, y = if_any_samples(temps, sampled, greedy, target_logits)
    if K > 0:
        padded = jnp.pad(draft_tokens, ((0, 0), (0, 1)))
        out = jnp.where(jnp.arange(K + 1)[None, :] < n_acc[:, None],
                        padded, 0)
        out = out.at[rows, n_acc].set(y)
    else:
        out = y[:, None]
    return out.astype(jnp.int32), (n_acc + 1).astype(jnp.int32)


class DraftWorker:
    """The draft side of speculative decoding: an int4-quantized small
    model with its OWN paged KV pool (same ``PagedKVPool`` block-table
    machinery as the target, fp pages), kept in sync with the engine's
    committed sequences and asked for ``k`` proposals per decode row.

    One jitted fixed-shape ragged forward serves BOTH duties — catch-up
    chunks (committing prompt/accepted tokens the draft has not seen)
    and the k proposal steps (q_len = 1 rows) — so the draft compiles
    one executable, mirroring the engine's trace-count discipline.

    The pool's committed length per sequence IS the draft's sync state:
    ``sync`` drives it to the engine's ``cached_len`` before proposing,
    and ``commit`` rolls it back after verification (rejected
    candidates' K/V become garbage the next append overwrites).
    """

    def __init__(self, model, *, target_cfg, page_size, max_num_seqs,
                 max_pages_per_seq, num_pages, step_token_budget, q_block,
                 chunk_size, seed=0, quantized_mode="weight_only_int4",
                 interpret=None):
        self.cfg = cfg = model.config
        if cfg.vocab_size != target_cfg.vocab_size:
            raise ValueError(
                f"draft vocab {cfg.vocab_size} != target vocab "
                f"{target_cfg.vocab_size}: speculative verification "
                f"compares distributions over one vocabulary")
        self.params = extract_params(model)
        self.quantized_mode = quantized_mode
        if quantized_mode is not None:
            from ..quantization.low_bit import quantize_params
            self.params = quantize_params(self.params, quantized_mode)
        self.page_size = page_size
        self.max_num_seqs = max_num_seqs
        self.max_pages_per_seq = max_pages_per_seq
        self.q_block = q_block
        self.chunk_size = chunk_size
        self.step_token_budget = step_token_budget
        self.pool = PagedKVPool(
            cfg.num_hidden_layers, cfg.num_key_value_heads, cfg.head_dim,
            num_pages=num_pages, page_size=page_size,
            dtype=self.params["embed"].dtype)
        if interpret is None:
            from ..kernels import _on_tpu
            interpret = not _on_tpu()
        self._interpret = interpret
        self._base_key = jax.random.key(seed)
        self._launched = False
        #: jitted draft launches this worker issued (sync + propose) —
        #: the draft-side dispatch forensics the metrics snapshot exports.
        #: A whole k-step proposal round is ONE launch (the lax.scan
        #: burst); catch-up sync chunks stay one launch per chunk round.
        self.launches = 0
        #: per-k jitted proposal bursts (k is the scan trip count — one
        #: executable per distinct k, and an engine uses one k for life)
        self._propose_jits: dict = {}
        self._propose_launched = False
        self._build_fwd()

    # ------------------------------------------------------------------
    def _build_fwd(self):
        cfg = self.cfg
        ps = self.page_size
        qb = self.q_block
        T = self.step_token_budget
        PPS = self.max_pages_per_seq
        interpret = self._interpret

        def fwd(params, kv, tokens, positions, tbls, q_starts, q_lens,
                kv_lens, sample_idx, base_key, seeds, gpos, temps, top_ks,
                top_ps):
            # one ragged forward (the SHARED fp layer body — the same
            # function the engine's fp ragged step runs): rows are
            # chunks during sync, q_len=1 during the proposal loop —
            # one executable either way
            tok_row, live = _ragged_packing(q_starts, q_lens, T)
            h = params["embed"][tokens][None]                # [1, T, hid]
            new_kv = []
            for lyr, (Kp, Vp) in zip(params["layers"], kv):
                h, Kp, Vp = _ragged_fp_layer(
                    lyr, h, Kp, Vp, positions, tbls, tok_row, live,
                    q_starts, q_lens, kv_lens, cfg, ps, PPS, qb,
                    interpret)
                new_kv.append((Kp, Vp))
            h = _rms_norm(h, params["norm"], cfg.rms_norm_eps)
            logits = _logits(params, h[0, sample_idx], cfg)  # [R, V]
            keys = request_keys(base_key, seeds, gpos, DRAFT_TAG)
            tok = sample_rows(logits, keys, temps, top_ks, top_ps)
            probs = sampling_probs(logits, temps, top_ks, top_ps)
            return tok, probs, new_kv

        from ..kernels import _on_tpu
        donate = (1,) if _on_tpu() else ()
        self._fwd_jit = jax.jit(fwd, donate_argnums=donate)

    def decode_cache_size(self) -> int:
        """Compile count of the draft catch-up forward (expected: 1)."""
        try:
            return int(self._fwd_jit._cache_size())
        except Exception:
            return 1 if self._launched else 0

    def propose_cache_size(self) -> int:
        """Compile count of the k-step proposal burst (expected: 1 —
        one scan executable per engine-lifetime k)."""
        try:
            return sum(int(fn._cache_size())
                       for fn in self._propose_jits.values())
        except Exception:
            return 1 if self._propose_launched else 0

    def _build_propose(self, k):
        """ONE jitted ``lax.scan`` over the k proposal steps (ROADMAP
        item 4's last leftover): the q_len=1 rows, per-step packing,
        sampling and KV appends all live in the loop body, so a whole
        proposal round costs one host dispatch where the host loop paid
        k. The body reuses the same shared fp layer body / packing /
        sampling functions as the per-step path, and reproduces the
        host loop's cursor packing exactly (live rows pack first, one
        q_block each), so the draft's candidates and reported
        distributions match the unrolled launches.
        """
        cfg = self.cfg
        ps = self.page_size
        qb = self.q_block
        T = self.step_token_budget
        PPS = self.max_pages_per_seq
        interpret = self._interpret

        def burst(params, kv, tbls, cur0, base, spec_lens, seeds, gpos0,
                  temps, top_ks, top_ps, base_key):
            def body(carry, j):
                kv, cur = carry
                live = j < spec_lens                           # [R]
                q_lens = live.astype(jnp.int32)
                # the host loop's packing: live rows pack first, one
                # q_block of budget each; dead rows start past T
                starts_raw = (jnp.cumsum(q_lens) - q_lens) * qb
                q_starts = jnp.where(live, starts_raw, T)
                tok_buf = jnp.zeros((T,), jnp.int32) \
                    .at[q_starts].set(cur, mode="drop")
                pos_buf = jnp.zeros((T,), jnp.int32) \
                    .at[q_starts].set(base + j, mode="drop")
                kv_lens = jnp.where(live, base + j + 1, 0)
                tbl = jnp.where(live[:, None], tbls, NULL_PAGE)
                sample_idx = jnp.where(live, starts_raw, 0)
                tok_row, live_tok = _ragged_packing(q_starts, q_lens, T)
                h = params["embed"][tok_buf][None]
                new_kv = []
                for lyr, (Kp, Vp) in zip(params["layers"], kv):
                    h, Kp, Vp = _ragged_fp_layer(
                        lyr, h, Kp, Vp, pos_buf, tbl, tok_row, live_tok,
                        q_starts, q_lens, kv_lens, cfg, ps, PPS, qb,
                        interpret)
                    new_kv.append((Kp, Vp))
                h = _rms_norm(h, params["norm"], cfg.rms_norm_eps)
                logits = _logits(params, h[0, sample_idx], cfg)
                keys = request_keys(base_key, seeds, gpos0 + j, DRAFT_TAG)
                tok = sample_rows(logits, keys, temps, top_ks, top_ps)
                probs = sampling_probs(logits, temps, top_ks, top_ps)
                tok = jnp.where(live, tok, 0)
                return (new_kv, jnp.where(live, tok, cur)), (tok, probs)

            (kv, _), (toks, probs) = jax.lax.scan(
                body, (kv, cur0), jnp.arange(k, dtype=jnp.int32))
            return toks, probs, kv                 # [k, R], [k, R, V]

        from ..kernels import _on_tpu
        donate = (1,) if _on_tpu() else ()
        return jax.jit(burst, donate_argnums=donate)

    # ------------------------------------------------------------------
    # host-side lifecycle
    # ------------------------------------------------------------------
    def drop(self, seq_id):
        """Forget a sequence (finished / preempted / cancelled): frees
        its draft pool pages. Re-admission re-syncs from scratch."""
        if seq_id in self.pool:
            self.pool.free(seq_id)

    def _ensure(self, seq):
        if seq.seq_id not in self.pool:
            self.pool.allocate(seq.seq_id, 0)

    def _dispatch(self, rows, seeds, gpos, temps, top_ks, top_ps):
        """Pack one fixed-shape draft launch. ``rows`` maps row slot ->
        (tokens, start_pos) — q_len 0 rows are pad slots."""
        T, R, PPS = (self.step_token_budget, self.max_num_seqs,
                     self.max_pages_per_seq)
        qb = self.q_block
        tokens = np.zeros((T,), np.int32)
        positions = np.zeros((T,), np.int32)
        tbls = np.full((R, PPS), NULL_PAGE, np.int32)
        q_starts = np.full((R,), T, np.int32)
        q_lens = np.zeros((R,), np.int32)
        kv_lens = np.zeros((R,), np.int32)
        sample_idx = np.zeros((R,), np.int32)
        cursor = 0
        for i, ent in enumerate(rows):
            if ent is None:
                continue
            seq_id, toks, start = ent
            n = len(toks)
            if n == 0:
                continue
            tokens[cursor:cursor + n] = toks
            positions[cursor:cursor + n] = np.arange(start, start + n)
            tbls[i] = self.pool.padded_block_table(seq_id, PPS)
            q_starts[i] = cursor
            q_lens[i] = n
            kv_lens[i] = start + n
            sample_idx[i] = cursor + n - 1
            cursor += -(-n // qb) * qb
        assert cursor <= T, "draft launch overflow"
        self.launches += 1
        self._launched = True
        tok, probs, new_kv = self._fwd_jit(
            self.params, self.pool.kv, jnp.asarray(tokens),
            jnp.asarray(positions), jnp.asarray(tbls),
            jnp.asarray(q_starts), jnp.asarray(q_lens),
            jnp.asarray(kv_lens), jnp.asarray(sample_idx), self._base_key,
            jnp.asarray(seeds), jnp.asarray(gpos), jnp.asarray(temps),
            jnp.asarray(top_ks), jnp.asarray(top_ps))
        self.pool.kv = new_kv
        # tokens come to host (the proposal loop feeds them back and the
        # verifier packs them into the query buffer); the [R, V] probs
        # stay a DEVICE array — the verifier consumes them on-device
        return np.asarray(tok), probs

    def sync(self, seqs):
        """Drive every sequence's draft pool length to the engine's
        committed ``cached_len`` (chunked catch-up: fresh prompts, the
        consumed-but-unverified candidate of a fully-accepted round,
        preemption-recompute restarts). Multiple launches if the
        deficits exceed one step's token budget."""
        R = self.max_num_seqs
        zeros = np.zeros((R,), np.int32)
        zf = np.zeros((R,), np.float32)
        ones = np.ones((R,), np.float32)
        for seq in seqs:
            self._ensure(seq)
        while True:
            # deficits re-read from the pool each round: prepare_append
            # commits, so every dispatched launch makes progress
            rows = [None] * R
            budget = self.step_token_budget
            qb = self.q_block
            for i, seq in enumerate(seqs):
                dlen = self.pool.seq_len(seq.seq_id)
                deficit = seq.cached_len - dlen
                if deficit <= 0:
                    continue
                n = min(deficit, self.chunk_size, (budget // qb) * qb)
                if n < 1:
                    continue               # next launch picks it up
                budget -= -(-n // qb) * qb
                try:
                    self.pool.prepare_append(seq.seq_id, dlen + n)
                except PoolExhausted as e:
                    raise PoolExhausted(
                        f"draft pool exhausted syncing {seq.seq_id!r}: "
                        f"{e} — size the draft pool like the target's "
                        f"(LLMEngine draft_num_pages)") from e
                rows[i] = (seq.seq_id, seq.all_ids[dlen:dlen + n], dlen)
            if not any(r is not None for r in rows):
                break
            self._dispatch(rows, zeros, zeros, zf, zeros, ones)

    def propose(self, seqs, spec_lens, k):
        """Run up to ``k`` q_len=1 proposal steps over the synced rows
        in ONE jitted ``lax.scan`` burst (one host dispatch per spec
        round — ``launches`` rises by 1, not k); rows sit out
        iterations past their own ``spec_lens`` entry (no append, no
        claim). Returns ``(draft_tokens [n, k] host, draft_probs
        [R, k, V] DEVICE)`` — ``draft_tokens`` aligns with ``seqs``
        (the verifier packs them into its query buffer), the probs
        never round-trip through the host; slots past a row's spec_len
        hold garbage the rejection sampler provably never reads
        (candidate masking by ``spec_lens``). Sequences must be
        caught-up decode rows already synced to ``cached_len``."""
        n_rows = len(seqs)
        V = self.cfg.vocab_size
        R = self.max_num_seqs
        PPS = self.max_pages_per_seq
        d_toks = np.zeros((n_rows, k), np.int32)
        if k == 0 or not any(spec_lens):
            return d_toks, jnp.zeros((R, k, V), jnp.float32)
        seeds = np.zeros((R,), np.int32)
        gpos = np.zeros((R,), np.int32)
        temps = np.zeros((R,), np.float32)
        top_ks = np.zeros((R,), np.int32)
        top_ps = np.ones((R,), np.float32)
        cur = np.zeros((R,), np.int32)
        base = np.zeros((R,), np.int32)
        spec = np.zeros((R,), np.int32)
        tbls = np.full((R, PPS), NULL_PAGE, np.int32)
        for i, seq in enumerate(seqs):
            if spec_lens[i] > 0:
                self.pool.prepare_append(
                    seq.seq_id, seq.cached_len + spec_lens[i])
                tbls[i] = self.pool.padded_block_table(seq.seq_id, PPS)
            cur[i] = seq.all_ids[-1]
            base[i] = seq.cached_len
            spec[i] = spec_lens[i]
            seeds[i] = seq.seed or 0
            gpos[i] = len(seq.tokens)
            temps[i] = seq.temperature
            top_ks[i] = seq.top_k or 0
            top_ps[i] = 1.0 if seq.top_p is None else seq.top_p
        fn = self._propose_jits.get(k)
        if fn is None:
            fn = self._propose_jits[k] = self._build_propose(k)
        self.launches += 1
        self._launched = True
        self._propose_launched = True
        toks, probs, new_kv = fn(
            self.params, self.pool.kv, jnp.asarray(tbls),
            jnp.asarray(cur), jnp.asarray(base), jnp.asarray(spec),
            jnp.asarray(seeds), jnp.asarray(gpos), jnp.asarray(temps),
            jnp.asarray(top_ks), jnp.asarray(top_ps), self._base_key)
        self.pool.kv = new_kv
        toks = np.asarray(toks)                            # [k, R]
        for i in range(n_rows):
            s = spec_lens[i]
            if s > 0:
                d_toks[i, :s] = toks[:s, i]
        # [R, k, V]; stays a device array — the verifier consumes it
        return d_toks, jnp.transpose(probs, (1, 0, 2))

    def commit(self, seq_id, cached_old, accepted, spec_len):
        """Roll the draft pool back to the verified state: of the
        ``spec_len`` tokens the proposal loop appended (the row's last
        committed token + its first ``spec_len - 1`` candidates), the
        first ``min(accepted + 1, spec_len)`` survive — a fully-accepted
        round's last candidate was never consumed by the draft, so the
        next ``sync`` chunks it in."""
        if seq_id not in self.pool:
            return
        self.pool.rollback(seq_id,
                           cached_old + min(accepted + 1, spec_len))


__all__ = ["DraftWorker", "speculative_sample", "DRAFT_TAG", "ACCEPT_TAG",
           "FINAL_TAG"]
