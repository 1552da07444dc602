"""Ragged prefill megakernel (Pallas TPU): one launch per prefill
chunk, at model scope.

Prefill is the TTFT hot path (the disaggregated prefill pool and the
chunked-prefill scheduler exist to protect it), and the unfused ragged
layer body costs 6+ device ops PER LAYER per chunk: rms_norm, three
projection dots, rope table build + apply, the page scatter append, the
ragged-attention launch, o-proj and the mlp. Following MPK (PAPERS.md)
and the Ragged Paged Attention shape (packed ``[total_q, ...]`` rows
over paged KV), this module collapses the whole ragged
prologue/epilogue chain per layer:

    rms_norm -> qkv projection as ONE fused concat-dot (int8 weights
    dequantized in the prologue) -> rope at per-row positions (phase
    tables hoisted: computed once per STEP, not once per layer) ->
    KV append for the freshly computed chunk pages (fp scatter
    in-kernel via aliased pool outputs; int8 running-amax via the
    caller's ``_segmented_quant_append`` discipline, append-first) ->
    ragged paged attention (scalar-prefetched (q_start, q_len, kv_len)
    + block-row map, in-kernel causal masking, horizon page skipping,
    online-softmax VMEM scratch, int8 per-(head, page) scales) ->
    o-proj -> residual -> rms_norm -> fused gate|up concat-dot ->
    swiglu -> residual

and then lifts it to model scope with the PR 18 ``stack_layer_params``
/ ``lax.scan`` machinery (:func:`fused_prefill_model`): a whole prefill
chunk — and a spec-decode verification round, which rides the same
``q_len > 1`` ragged rows — costs O(1) launches instead of O(L*ops).

Two execution tiers, both honest about what ran:

- the **jnp fused body** (:func:`_reference_prefill_layer`) is a
  BITWISE-identical restructuring of the unfused ragged layer
  (serving/spec_decode._ragged_fp_layer and the engine's int8 body):
  a fused concat-dot sliced per projection equals the per-projection
  dots bit for bit (same per-output-column reduction, fp and int8
  per-column scales alike), the hoisted rope/slot/block-row prologue
  (:func:`ragged_prologue`) replays the exact per-layer derivations,
  and the LoRA delta is added per projection slice in the same
  base-plus-delta order — so ``FLAGS_prefill_megakernel=fused`` keeps
  token output byte-identical on every backend. This is the tier the
  CPU bitwise gates pin.
- the **Pallas kernel** (:func:`fused_prefill_layer` on TPU /
  interpreter) runs the whole chain as ONE launch over grid
  (q_block index, kv-head group, logical page), with the chunk's
  freshly-roped K/V staged in VMEM scratch and overlaid on the page
  stream ahead of the pool write landing — parity-tested against the
  jnp body at fp tolerance (the PR 18 honest split: kernels are
  tolerance-tested, engines are bitwise-gated on the jnp tier).

fp KV append lands IN-KERNEL through ``input_output_aliases``: the
pool operands alias the pool outputs, and every (block, page) visit
rewrites the addressed page as ``where(chunk_overlay_valid, fresh_kv,
committed)`` — committed rows copy through unchanged, chunk rows take
the scratch-staged values, and revisits are idempotent (each rewrite
depends only on scratch + committed rows, never on a prior rewrite),
so the clamped dead-page revisits the ragged kernel uses for DMA
elision stay safe. int8 pools keep the append OUTSIDE the kernel
(``quant_append_fn`` — the running-amax requant must be visible to the
attention gather, decode_megakernel's ``self_kv=False`` contract).
The NULL/trash page (serving.kv_cache.NULL_PAGE) is left as it was, as
``paged_attention.kv_append`` leaves it: dead-token rows are dropped,
and what the page holds is unspecified by contract and never read back.

int4 weights (and any mixed layouts) have no fused-weight geometry:
:func:`fuse_layer_weights` returns None and the engine keeps the
unfused bodies — :func:`prefill_megakernel_mode` reports ``jnp`` so the
bench artifact never fabricates a kernel that does not run.
"""
from __future__ import annotations

import collections
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_megakernel import (_rms, _swap_matrix,
                               whole_weight_vmem_limit)

_NEG_INF = -1e30

# the fused projection layout: qkv and gate|up collapse to concat-dots,
# o and down stay single matrices
_FUSED_MATS = ("qkv", "o", "gateup", "down")

# process-wide record of a runtime Pallas failure rerouted to the jnp
# body by FLAGS_enable_fusion_fallback (decode_megakernel's discipline)
_FALLBACK = {"tripped": False}


def prefill_fallback_tripped() -> bool:
    """True once a prefill Pallas launch failed at runtime and
    ``FLAGS_enable_fusion_fallback`` rerouted it to the jnp body."""
    return _FALLBACK["tripped"]


def reset_prefill_fallback() -> None:
    """Clear the tripped-fallback record (tests; engine re-init)."""
    _FALLBACK["tripped"] = False


def _fused_kernel_ready(fused):
    """fp arrays or all-int8 QuantizedWeight across the fused mats ->
    the kernel handles it; anything else takes the jnp body."""
    from ..quantization.low_bit import QuantizedWeight
    if fused is None:
        return None
    kinds = set()
    for k in _FUSED_MATS:
        w = fused[k]
        if isinstance(w, QuantizedWeight):
            if w.bits != 8:
                return None
            kinds.add("int8")
        else:
            kinds.add("fp")
    if len(kinds) != 1:
        return None
    return kinds.pop()


def prefill_megakernel_mode(fused=None, interpret=None) -> str:
    """How :func:`fused_prefill_layer` would execute here: ``pallas``
    (TPU), ``interpret`` (forced Pallas interpreter), or ``jnp`` (the
    bitwise fused body) — the bench artifact's honesty field.

    Pass the :func:`fuse_layer_weights` result to report the mode ITS
    geometry selects (None — int4/mixed — is always ``jnp``); pass
    ``interpret`` when the caller pinned the mode explicitly."""
    if fused is None or _fused_kernel_ready(fused) is None:
        return "jnp"
    if _FALLBACK["tripped"]:
        from ..core.flags import GLOBAL_FLAGS
        if GLOBAL_FLAGS.get("enable_fusion_fallback"):
            return "jnp"
    if interpret is True:
        return "interpret"
    from . import _on_tpu
    if _on_tpu():
        return "pallas"
    if interpret is None:
        interpret = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
    return "interpret" if interpret else "jnp"


def fuse_layer_weights(layer):
    """Concatenate one decoder layer's projections into the fused
    layout ``{ln1, ln2, qkv, o, gateup, down}``.

    The q/k/v (and gate/up) matrices share their input dimension, so
    ``x @ concat([Wq, Wk, Wv], axis=1)`` sliced back per projection is
    BITWISE the three separate dots — each output column is the same
    reduction either way. int8 ``QuantizedWeight`` concatenates exactly
    too: the dequant scale is per OUTPUT column, so qdata and scale
    concatenate along the same axis. int4 (packed nibbles) and mixed
    layouts have no column-exact concat — returns None and the caller
    keeps the unfused bodies.
    """
    from ..quantization.low_bit import QuantizedWeight

    def kind(w):
        if isinstance(w, QuantizedWeight):
            return "int8" if w.bits == 8 else None
        return "fp"

    kinds = {kind(layer[k]) for k in
             ("q", "k", "v", "o", "gate", "up", "down")}
    if len(kinds) != 1 or None in kinds:
        return None

    def cat(keys):
        ws = [layer[k] for k in keys]
        if isinstance(ws[0], QuantizedWeight):
            return QuantizedWeight(
                jnp.concatenate([w.qdata for w in ws], axis=1),
                jnp.concatenate(
                    [jnp.asarray(w.scale).reshape(-1) for w in ws]),
                ws[0].bits, ws[0].rows)
        return jnp.concatenate(ws, axis=1)

    return {"ln1": layer["ln1"], "ln2": layer["ln2"],
            "qkv": cat(("q", "k", "v")), "o": layer["o"],
            "gateup": cat(("gate", "up")), "down": layer["down"]}


#: the layer-invariant ragged prologue, computed ONCE per step and
#: shared by every layer's fused body: rope phase tables at the packed
#: per-row positions, the page-slot scatter map (dead tokens -> the
#: null page), and the attention block-row map
RaggedPrologue = collections.namedtuple(
    "RaggedPrologue", ["cos", "sin", "slot", "block_row"])


def _rank_right(q_starts, v):
    """``searchsorted(q_starts, v, side="right") - 1`` clamped at 0, as
    one broadcast compare-sum: for ascending ``q_starts`` (duplicates
    included) the right-insertion point IS the count of starts <= v, so
    the integers are identical — but the compare-sum fuses into the
    surrounding elementwise work while ``jnp.searchsorted`` lowers to a
    sequential ``while`` loop that stays a standalone entry kernel."""
    rank = jnp.sum(q_starts[None, :] <= v[:, None], axis=1,
                   dtype=jnp.int32) - 1
    return jnp.maximum(rank, 0)


def ragged_prologue(positions, tbls, q_starts, q_lens, *,
                    theta, head_dim, page_size, max_pages, q_block):
    """Derive the :class:`RaggedPrologue` for one ragged step. Every
    field is VALUE-identical to the unfused layer body's per-layer
    derivations (models.generation._rope's table build,
    _ragged_fp_layer's slot chain, paged_attention's block-row
    derivation) — the rope/slot chains replay the exact ops, and the
    integer row maps come from :func:`_rank_right` (exact index math,
    no float in sight) — so consuming them from here is bitwise-neutral
    for the tokens while paying the derivations once per STEP instead
    of once per layer, with the two searchsorted ``while`` kernels
    replaced by fusable compares."""
    from ..serving.kv_cache import NULL_PAGE
    d = head_dim
    T = positions.shape[0]
    pos = positions[None]                                    # [1, T]
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv_freq      # [1, T, d/2]
    cos = jnp.cos(ang)[:, :, None, :]                        # [1,T,1,d/2]
    sin = jnp.sin(ang)[:, :, None, :]
    q_starts = jnp.asarray(q_starts, jnp.int32)
    tok_row = _rank_right(q_starts, jnp.arange(T, dtype=jnp.int32))
    live = (jnp.arange(T) - q_starts[tok_row]) < q_lens[tok_row]
    page_idx = jnp.clip(positions // page_size, 0, max_pages - 1)
    page = jnp.where(live, tbls[tok_row, page_idx], NULL_PAGE)
    slot = page * page_size + positions % page_size
    block_row = _rank_right(
        q_starts, jnp.arange(T // q_block, dtype=jnp.int32) * q_block)
    return RaggedPrologue(cos, sin, slot, block_row)


def rope_apply(x, cos, sin):
    """Apply precomputed interleaved-pair phase tables — the apply half
    of models.generation._rope verbatim, so ``rope_apply(x, *tables)``
    is bitwise ``_rope(x, positions, theta, d)`` when the tables came
    from :func:`ragged_prologue` at the same positions."""
    x1 = x[..., ::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.stack([o1, o2], axis=-1).reshape(x.shape).astype(x.dtype)


def _slice_qkv(fused):
    """The k|v tail of the fused qkv matrix as its own operand —
    column-slicing a (possibly quantized) weight is exact because both
    the dot and the dequant scale are per output column."""
    from ..quantization.low_bit import QuantizedWeight
    w = fused["qkv"]
    if isinstance(w, QuantizedWeight):
        def sl(lo, hi):
            return QuantizedWeight(
                w.qdata[:, lo:hi],
                jnp.asarray(w.scale).reshape(-1)[lo:hi],
                w.bits, w.rows)
        return sl
    def sl(lo, hi):
        return w[:, lo:hi]
    return sl


def _reference_prefill_layer(fused, h, Kp, Vp, tbls, pre, q_starts,
                             q_lens, kv_lens, *, eps, num_heads,
                             num_kv_heads, head_dim, page_size, q_block,
                             attn_interpret, k_scales=None, v_scales=None,
                             quant_append_fn=None, adapters=None,
                             slots=None):
    """The fused jnp body: a bitwise restructuring of the unfused
    ragged layer (fp: spec_decode._ragged_fp_layer; int8: the engine's
    inline body). Projections run as concat-dots sliced back per
    projection, rope/slot/block-row come precomputed off ``pre``, and
    LoRA deltas add per slice in _wmat's base-plus-delta order.
    Returns ``(h, Kp, Vp, k_scales, v_scales)`` (scales None for fp
    pools)."""
    from ..models.generation import _lora_delta, _rms_norm, _wmat
    H, Hkv, d = num_heads, num_kv_heads, head_dim
    T = h.shape[1]
    F = fused["gateup"].shape[-1] // 2

    def lo(p):
        if adapters is None:
            return None
        A, B = adapters[p]
        return (A, B, slots)

    def delta(y, x, p):
        if adapters is None:
            return y
        return y + _lora_delta(x, lo(p)).astype(y.dtype)

    x = _rms_norm(h, fused["ln1"], eps)
    qkv = _wmat(x, fused["qkv"])
    q = delta(qkv[..., :H * d], x, "q").reshape(1, T, H, d)
    k = delta(qkv[..., H * d:(H + Hkv) * d], x, "k").reshape(1, T, Hkv, d)
    v = delta(qkv[..., (H + Hkv) * d:], x, "v").reshape(1, T, Hkv, d)
    q = rope_apply(q, pre.cos, pre.sin)
    k = rope_apply(k, pre.cos, pre.sin)
    kt = jnp.transpose(k[0], (1, 0, 2))                  # [Hkv, T, d]
    vt = jnp.transpose(v[0], (1, 0, 2))
    from .paged_attention import kv_append, ragged_paged_attention
    if quant_append_fn is not None:
        # int8 pools: append-first — the running-amax requant must be
        # visible to the attention gather (the engine owns the
        # segmented append, threaded in as a callback)
        Kp, k_scales, Vp, v_scales = quant_append_fn(
            Kp, k_scales, Vp, v_scales, kt, vt)
    else:
        Kp = kv_append(Kp, pre.slot, kt, interpret=attn_interpret)
        Vp = kv_append(Vp, pre.slot, vt, interpret=attn_interpret)
    o = ragged_paged_attention(q[0], Kp, Vp, tbls, q_starts, q_lens,
                               kv_lens, q_block=q_block,
                               interpret=attn_interpret,
                               k_scales=k_scales, v_scales=v_scales,
                               block_row=pre.block_row)
    from ..core.flags import GLOBAL_FLAGS
    if GLOBAL_FLAGS.get("fusion_probe_barrier"):
        # the fusion-forensics injected regression, fused edition: same
        # seam (attention -> o-proj) as the unfused body
        (o,) = jax.lax.optimization_barrier((o,))
    h = h + _wmat(o.reshape(1, T, H * d), fused["o"], lora=lo("o"))
    x = _rms_norm(h, fused["ln2"], eps)
    gu = _wmat(x, fused["gateup"])
    gate = delta(gu[..., :F], x, "gate")
    up = delta(gu[..., F:], x, "up")
    h = h + _wmat(jax.nn.silu(gate) * up, fused["down"], lora=lo("down"))
    return h, Kp, Vp, k_scales, v_scales


def _build_prefill_kernel(*, H, Hkv, grp, dh, ps, T, G, hb, qb,
                          quant_w, quant_kv, eps, scale):
    """One closure per (layout, shape) variant. Grid = (q block,
    kv-head group, logical page); VMEM scratch carries the roped
    queries, the chunk's fresh K/V (fp pools), and the online-softmax
    state across the sequential page axis."""
    span = T + 2 * ps      # per-kv-head chunk scratch rows (+-ps pad so
                           # the page overlay slice clamps in-bounds)

    def kernel(*refs):
        it = iter(refs)
        row_ref = next(it)
        qs_ref = next(it)
        ql_ref = next(it)
        kl_ref = next(it)
        tbl_ref = next(it)
        ks_ref = vs_ref = None
        if quant_kv:
            ks_ref = next(it)
            vs_ref = next(it)
        h_ref = next(it)
        cos_ref = next(it)
        sin_ref = next(it)
        ln1_ref = next(it)
        ln2_ref = next(it)

        def w_pair():
            w = next(it)
            s = next(it) if quant_w else None
            return w, s

        wqkv = w_pair()
        wo = w_pair()
        wgu = w_pair()
        wd = w_pair()
        kpg_ref = next(it)
        vpg_ref = next(it)
        hout_ref = next(it)
        kout_ref = vout_ref = None
        kc_scr = vc_scr = None
        if not quant_kv:
            kout_ref = next(it)
            vout_ref = next(it)
        q_scr = next(it)
        if not quant_kv:
            kc_scr = next(it)
            vc_scr = next(it)
        m_scr = next(it)
        l_scr = next(it)
        acc_scr = next(it)

        i = pl.program_id(0)          # q block
        g = pl.program_id(1)          # kv-head group
        p = pl.program_id(2)          # logical page of the block's row
        row = row_ref[i]
        qs = qs_ref[row]
        ql = ql_ref[row]
        kl = kl_ref[row]
        kv_start = kl - ql
        blk_off = i * qb - qs

        def mat(pair):
            w_ref, s_ref = pair
            w = w_ref[...].astype(jnp.float32)
            if s_ref is not None:
                w = w * s_ref[...]
            return w

        def dot(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when((g == 0) & (p == 0))
        def _prologue():
            hv = h_ref[...].astype(jnp.float32)              # [qb, D]
            cosv = cos_ref[...].astype(jnp.float32)          # [qb, dh]
            sinv = sin_ref[...].astype(jnp.float32)
            swap = _swap_matrix(dh)
            x = _rms(hv, ln1_ref[...].astype(jnp.float32), eps)
            qkv = dot(x, mat(wqkv))            # [qb, (H + 2*Hkv)*dh]
            for hh in range(H):                # static head loop
                qh = qkv[:, hh * dh:(hh + 1) * dh]
                qh = qh * cosv + dot(qh, swap) * sinv
                q_scr[pl.ds(hh * qb, qb), :] = qh
            if not quant_kv:
                # stage the chunk's fresh roped K / raw V at this
                # block's PACKED row range; pages overlay it below
                for hh in range(Hkv):
                    kh = qkv[:, (H + hh) * dh:(H + hh + 1) * dh]
                    kh = kh * cosv + dot(kh, swap) * sinv
                    vh = qkv[:, (H + Hkv + hh) * dh:
                             (H + Hkv + hh + 1) * dh]
                    off = hh * span + ps + i * qb
                    kc_scr[pl.ds(off, qb), :] = kh
                    vc_scr[pl.ds(off, qb), :] = vh
            m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        base = p * ps
        last_live = jnp.maximum(kl - 1, 0) // ps
        # the PHYSICAL page this visit addresses (dead pages clamp to
        # the last live one — the ragged kernel's DMA-elision trick)
        base_eff = jnp.minimum(p, last_live) * ps
        horizon = jnp.minimum(kl, kv_start + blk_off + qb)
        live_block = (blk_off >= 0) & (blk_off < ql)

        def overlay(hh, base_v, page_k, page_v):
            """Chunk-scratch overlay of one addressed page: committed
            rows copy through, rows this chunk owns (and this block has
            already staged) take the fresh scratch values."""
            off = hh * span
            start = jnp.clip(qs + base_v - kv_start + ps, 0, T + ps)
            ovk = kc_scr[pl.ds(off + start, ps), :]
            ovv = vc_scr[pl.ds(off + start, ps), :]
            jj = jax.lax.broadcasted_iota(jnp.int32, (ps, 1), 0)
            t = base_v - kv_start + jj
            valid = (t >= 0) & (t < ql) & (t < blk_off + qb)
            return (jnp.where(valid, ovk, page_k),
                    jnp.where(valid, ovv, page_v))

        if not quant_kv:
            # fp in-kernel append: EVERY visit rewrites the page it
            # addressed through the aliased outputs — committed rows
            # unchanged, chunk rows fresh. Idempotent across the
            # clamped revisits (depends only on scratch + committed
            # rows), and the final visitor of each page has staged its
            # full valid range, so the pool converges to exactly the
            # jnp body's bytes for every live page.
            for j in range(hb):
                hh = g * hb + j
                pk = kpg_ref[j, 0].astype(jnp.float32)       # [ps, dh]
                pv = vpg_ref[j, 0].astype(jnp.float32)
                nk, nv = overlay(hh, base_eff, pk, pv)
                kout_ref[j, 0] = nk.astype(kout_ref.dtype)
                vout_ref[j, 0] = nv.astype(vout_ref.dtype)

        @pl.when(live_block & (base < horizon))
        def _page():
            for j in range(hb):                  # static head loop
                hh = g * hb + j
                kj = kpg_ref[j, 0].astype(jnp.float32)       # [ps, dh]
                vj = vpg_ref[j, 0].astype(jnp.float32)
                if quant_kv:
                    page_id = tbl_ref[row, jnp.minimum(p, last_live)]
                    kj = kj * ks_ref[hh, page_id]
                    vj = vj * vs_ref[hh, page_id]
                else:
                    # attention must see the chunk's fresh rows even
                    # before the aliased write lands: read them off the
                    # scratch overlay (base == base_eff here: the page
                    # axis only runs below the causal horizon)
                    kj, vj = overlay(hh, base, kj, vj)
                row0 = hh * grp * qb
                qj = q_scr[pl.ds(row0, grp * qb), :]
                s = jax.lax.dot_general(
                    qj, kj, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s3 = s.reshape(grp, qb, ps)
                tok = blk_off + jax.lax.broadcasted_iota(
                    jnp.int32, s3.shape, 1)
                pos = base + jax.lax.broadcasted_iota(
                    jnp.int32, s3.shape, 2)
                ok = (tok < ql) & (pos <= kv_start + tok) & (pos < kl)
                s = jnp.where(ok, s3, _NEG_INF).reshape(grp * qb, ps)
                mj = m_scr[pl.ds(row0, grp * qb), :]
                lj = l_scr[pl.ds(row0, grp * qb), :]
                aj = acc_scr[pl.ds(row0, grp * qb), :]
                m_cur = jnp.max(s, axis=1, keepdims=True)
                m_new = jnp.maximum(mj, m_cur)
                alpha = jnp.exp(mj - m_new)
                e = jnp.exp(s - m_new)
                l_scr[pl.ds(row0, grp * qb), :] = \
                    lj * alpha + jnp.sum(e, axis=1, keepdims=True)
                m_scr[pl.ds(row0, grp * qb), :] = m_new
                acc_scr[pl.ds(row0, grp * qb), :] = aj * alpha + dot(e, vj)

        @pl.when((g == G - 1) & (p == pl.num_programs(2) - 1))
        def _epilogue():
            o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
            o = o.reshape(H, qb, dh).transpose(1, 0, 2) \
                .reshape(qb, H * dh)
            hv = h_ref[...].astype(jnp.float32)
            h2 = hv + dot(o, mat(wo))
            x2 = _rms(h2, ln2_ref[...].astype(jnp.float32), eps)
            gu = dot(x2, mat(wgu))
            Fh = gu.shape[1] // 2
            mlp = dot(jax.nn.silu(gu[:, :Fh]) * gu[:, Fh:], mat(wd))
            hout_ref[...] = (h2 + mlp).astype(hout_ref.dtype)

    return kernel


def _pick_groups(Hkv, key_dims, run_fn, traced):
    from .autotune import autotune_enabled, pick_cached
    default = {"head_groups": 1}
    if not autotune_enabled() or Hkv == 1:
        return default
    cands = [{"head_groups": g} for g in range(1, Hkv + 1) if Hkv % g == 0]
    # the prefill key carries (q_block, scope, num_layers) geometry so
    # prefill/decode and layer/model tilings never alias a stale
    # recorded block size (kernels/autotune.py key separation)
    return pick_cached(key=("prefill_megakernel",) + tuple(key_dims),
                       requested=default, candidates=cands,
                       build_fn=lambda c: (lambda: run_fn(c)),
                       traced=traced)


def fused_prefill_layer(fused, h, Kp, Vp, tbls, pre, q_starts, q_lens,
                        kv_lens, *, eps, num_heads, q_block,
                        interpret=None, attn_interpret=False,
                        k_scales=None, v_scales=None,
                        quant_append_fn=None, adapters=None, slots=None,
                        scope="layer", num_layers=1):
    """One fused decoder layer over a packed ragged chunk.

    fused: :func:`fuse_layer_weights` result (ln1/ln2 + qkv/o/gateup/
        down, fp or all-int8);
    h: [1, T, hidden] packed token hidden states; Kp/Vp:
        [Hkv, num_pages, page_size, dh] pools; tbls: [R, PPS] int32;
    pre: the step-hoisted :class:`RaggedPrologue`;
    q_starts/q_lens/kv_lens: [R] int32, the ragged attention metadata
        (kv_lens AFTER this step's appends).
    interpret: the KERNEL-mode knob (decode_megakernel semantics: None
        is env-driven, True pins the Pallas interpreter); the jnp body
        runs whenever no kernel applies. attn_interpret: what the jnp
        body forwards to its inner ragged_paged_attention call (the
        engine's attention interpret knob — kept separate so the fused
        body is bitwise the unfused one on every backend).
    quant_append_fn(Kp, Ks, Vp, Vs, kt, vt) -> (Kp, Ks, Vp, Vs): the
        int8 running-amax requant-append for this layer, run BEFORE
        attention (caller-owned). fp pools append internally — the jnp
        body through ``kv_append`` at ``pre.slot``; the kernel writes
        pages through aliased outputs.
    adapters/slots: the layer's LoRA slab + per-token slot ids (jnp
        body only; their presence routes away from the kernel).
    Returns ``(h, Kp, Vp, k_scales, v_scales)``.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    quant_kv = k_scales is not None
    if quant_kv and quant_append_fn is None:
        raise ValueError("int8 pools need quant_append_fn (the caller "
                         "owns the running-amax append)")
    H = num_heads
    Hkv, npages, ps, dh = Kp.shape
    T = h.shape[1]
    D = h.shape[2]
    q_starts = jnp.asarray(q_starts, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    tbls = jnp.asarray(tbls, jnp.int32)

    forced = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
    from . import _on_tpu
    on_tpu = _on_tpu()
    if interpret is None:
        interpret = forced and not on_tpu
    kind = _fused_kernel_ready(fused)

    def reference():
        return _reference_prefill_layer(
            fused, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
            eps=eps, num_heads=H, num_kv_heads=Hkv, head_dim=dh,
            page_size=ps, q_block=q_block, attn_interpret=attn_interpret,
            k_scales=k_scales, v_scales=v_scales,
            quant_append_fn=quant_append_fn, adapters=adapters,
            slots=slots)

    if not ((on_tpu or interpret) and kind is not None
            and adapters is None):
        return reference()

    quant_w = kind == "int8"
    grp = H // Hkv
    PPS = tbls.shape[1]
    scale = 1.0 / (dh ** 0.5)
    nb = T // q_block
    qb = q_block
    # full-dim phase tables for the swap-matmul rope (pairs (2i, 2i+1)
    # share frequency i)
    cosf = jnp.repeat(pre.cos[0, :, 0, :], 2, axis=1)        # [T, dh]
    sinf = jnp.repeat(pre.sin[0, :, 0, :], 2, axis=1)
    h2d = h[0]                                               # [T, D]

    Ksq = Vsq = None
    KpK, VpK = Kp, Vp
    if quant_kv:
        # int8 append-first prologue OUTSIDE the kernel: project k/v
        # off the column-sliced fused weight (column slices of a
        # concat-dot are exact), rope, and requant-append so the
        # kernel's gather sees the updated pool + scales
        from ..models.generation import _rms_norm, _wmat
        sl = _slice_qkv(fused)
        x = _rms_norm(h, fused["ln1"], eps)
        k = _wmat(x, sl(H * dh, (H + Hkv) * dh)).reshape(1, T, Hkv, dh)
        v = _wmat(x, sl((H + Hkv) * dh, (H + 2 * Hkv) * dh)) \
            .reshape(1, T, Hkv, dh)
        k = rope_apply(k, pre.cos, pre.sin)
        kt = jnp.transpose(k[0], (1, 0, 2))
        vt = jnp.transpose(v[0], (1, 0, 2))
        KpK, Ksq, VpK, Vsq = quant_append_fn(Kp, k_scales, Vp, v_scales,
                                             kt, vt)

    def kv_map_for(hb):
        def kv_map(i, g, p, rows, qs, ql, kl, tbl, *scales):
            row = rows[i]
            last = jnp.maximum(kl[row] - 1, 0) // ps
            return (g, tbl[row, jnp.minimum(p, last)], 0, 0)
        return kv_map

    def row_map(i, g, p, *pf):
        return (i, 0)

    def const_map(i, g, p, *pf):
        return (0, 0)

    def wop(key):
        w = fused[key]
        if quant_w:
            qd = w.qdata
            sc = jnp.asarray(w.scale, jnp.float32).reshape(1, -1)
            return [qd, sc], [
                pl.BlockSpec(qd.shape, const_map),
                pl.BlockSpec(sc.shape, const_map)]
        return [w], [pl.BlockSpec(w.shape, const_map)]

    def run(cfg):
        G = int(cfg["head_groups"])
        hb = Hkv // G
        kernel = _build_prefill_kernel(
            H=H, Hkv=Hkv, grp=grp, dh=dh, ps=ps, T=T, G=G, hb=hb,
            qb=qb, quant_w=quant_w, quant_kv=quant_kv, eps=float(eps),
            scale=scale)
        operands = [h2d, cosf, sinf,
                    jnp.asarray(fused["ln1"]).reshape(1, D),
                    jnp.asarray(fused["ln2"]).reshape(1, D)]
        in_specs = [pl.BlockSpec((qb, D), row_map),
                    pl.BlockSpec((qb, dh), row_map),
                    pl.BlockSpec((qb, dh), row_map),
                    pl.BlockSpec((1, D), const_map),
                    pl.BlockSpec((1, D), const_map)]
        for key in _FUSED_MATS:
            ops, specs = wop(key)
            operands += ops
            in_specs += specs
        prefetch = [pre.block_row, q_starts, q_lens, kv_lens, tbls]
        if quant_kv:
            prefetch += [jnp.asarray(Ksq, jnp.float32),
                         jnp.asarray(Vsq, jnp.float32)]
        kv_idx = len(prefetch) + len(operands)
        operands += [KpK, VpK]
        in_specs += [pl.BlockSpec((hb, 1, ps, dh), kv_map_for(hb)),
                     pl.BlockSpec((hb, 1, ps, dh), kv_map_for(hb))]
        out_shape = [jax.ShapeDtypeStruct((T, D), h.dtype)]
        out_specs = [pl.BlockSpec((qb, D), row_map)]
        aliases = {}
        if not quant_kv:
            out_shape += [jax.ShapeDtypeStruct(Kp.shape, Kp.dtype),
                          jax.ShapeDtypeStruct(Vp.shape, Vp.dtype)]
            out_specs += [pl.BlockSpec((hb, 1, ps, dh), kv_map_for(hb)),
                          pl.BlockSpec((hb, 1, ps, dh), kv_map_for(hb))]
            # the in-kernel fp append: pool operands alias pool outputs
            aliases = {kv_idx: 1, kv_idx + 1: 2}
        scratch = [pltpu.VMEM((H * qb, dh), jnp.float32)]    # roped q
        if not quant_kv:
            span = T + 2 * ps
            scratch += [pltpu.VMEM((Hkv * span, dh), jnp.float32),
                        pltpu.VMEM((Hkv * span, dh), jnp.float32)]
        scratch += [pltpu.VMEM((H * qb, 1), jnp.float32),    # m
                    pltpu.VMEM((H * qb, 1), jnp.float32),    # l
                    pltpu.VMEM((H * qb, dh), jnp.float32)]   # acc
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(nb, G, PPS),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, name="fused_prefill_layer",
            input_output_aliases=aliases,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=whole_weight_vmem_limit(
                    operands[:-2])),     # all but the HBM-paged pools
        )(*prefetch, *operands)
        return out

    traced = any(isinstance(a, jax.core.Tracer) for a in (h, Kp, kv_lens))
    cfg = _pick_groups(
        Hkv, (T, D, H, Hkv, dh, PPS, ps, kind, bool(quant_kv),
              int(q_block), str(scope), int(num_layers)), run, traced)
    try:
        out = run(cfg)
    except Exception:
        from ..core.flags import GLOBAL_FLAGS
        if not GLOBAL_FLAGS.get("enable_fusion_fallback"):
            raise
        _FALLBACK["tripped"] = True
        from ..core.vlog import vlog
        vlog(0, "pallas prefill megakernel failed; falling back to the "
                "jnp fused body (FLAGS_enable_fusion_fallback)")
        return reference()
    if quant_kv:
        return out[0][None], KpK, VpK, Ksq, Vsq
    h_out, Kn, Vn = out
    return h_out[None], Kn, Vn, None, None


def fused_prefill_model(layers, h, k_pages, v_pages, tbls, pre,
                        q_starts, q_lens, kv_lens, *, eps, num_heads,
                        q_block, interpret=None, attn_interpret=False,
                        k_scales=None, v_scales=None,
                        quant_append_fn=None, adapters=None, slots=None):
    """Whole-model ragged prefill: ``lax.scan`` of the fused layer body
    over stacked ``[L, ...]`` fused weights (stack_layer_params over
    :func:`fuse_layer_weights` results) and stacked pools — ONE
    layer-body site in the lowered program, so a whole prefill chunk
    (or spec-decode verification round) costs O(1) launches.

    k_pages/v_pages: ``[L, Hkv, num_pages, ps, dh]`` stacked pools;
    k_scales/v_scales: ``[L, Hkv, num_pages]`` stacked int8 scales
    (with quant_append_fn, run per layer slice inside the scan);
    adapters: stacked ``[L, ...]`` LoRA slab tree or None. Returns
    ``(h, k_pages, v_pages, k_scales, v_scales)`` with stacked pools.
    """
    num_layers = int(k_pages.shape[0])

    def _layer(lyr, ad, hc, Kp, Vp, Ks=None, Vs=None):
        return fused_prefill_layer(
            lyr, hc, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
            eps=eps, num_heads=num_heads, q_block=q_block,
            interpret=interpret, attn_interpret=attn_interpret,
            k_scales=Ks, v_scales=Vs, quant_append_fn=quant_append_fn,
            adapters=ad, slots=slots, scope="model",
            num_layers=num_layers)

    if k_scales is None:
        def body(hc, xs):
            lyr, ad, Kp, Vp = xs
            hc, Kp, Vp, _, _ = _layer(lyr, ad, hc, Kp, Vp)
            return hc, (Kp, Vp)
        h, (Kn, Vn) = jax.lax.scan(
            body, h, (layers, adapters, k_pages, v_pages))
        return h, Kn, Vn, None, None

    def body(hc, xs):
        lyr, ad, Kp, Vp, Ks, Vs = xs
        hc, Kp, Vp, Ks, Vs = _layer(lyr, ad, hc, Kp, Vp, Ks, Vs)
        return hc, (Kp, Vp, Ks, Vs)
    h, (Kn, Vn, Ksn, Vsn) = jax.lax.scan(
        body, h, (layers, adapters, k_pages, v_pages, k_scales,
                  v_scales))
    return h, Kn, Vn, Ksn, Vsn


__all__ = ["RaggedPrologue", "fuse_layer_weights", "fused_prefill_layer",
           "fused_prefill_model", "prefill_fallback_tripped",
           "prefill_megakernel_mode", "ragged_prologue",
           "reset_prefill_fallback", "rope_apply"]
