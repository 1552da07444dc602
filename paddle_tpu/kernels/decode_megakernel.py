"""Decode megakernel (Pallas TPU): one launch per decoder layer, and —
via :func:`fused_decode_model` — one launch per token (scan-over-layers
inside the kernel tier).

Decode is dispatch-bound: a single generated token used to cost 4+
device ops PER LAYER (rms_norm, qkv projection, paged-attention gather,
o projection, mlp) plus a host round-trip per token. Following "MPK: A
Compiler and Runtime for Mega-Kernelizing Tensor Programs" and
"Operator Fusion in XLA" (PAPERS.md), this module collapses the whole
decode layer body into ONE persistent Pallas kernel:

    rms_norm -> qkv projection (int8 weights dequantized in the
    prologue, the kernels/int8_matmul.py discipline) -> rope ->
    paged-attention gather over the sequence's live pages (int8
    per-(head, page) KV dequant riding the scalar-prefetch channel,
    the kernels/paged_attention.py discipline) -> o projection ->
    residual add -> rms_norm -> swiglu MLP -> residual add

Grid = (row block, row in block, kv-head group, logical page): the page
axis is innermost and sequential, so VMEM scratch carries the
online-softmax state (m, l, acc) and the roped queries across pages —
HBM page reads scale with true kv length exactly like the ragged
kernel. Row operands (h, rope tables, outputs) move in blocks of
``_ROW_BLOCK`` = 8 f32 rows — one row is not a legal TPU tile — with R
padded up to a multiple of it (pad rows: kv_len 0, null-page tables);
the kernel picks its row out of the resident block by a dynamic sublane
index. The projection prologue runs once per row at (group 0, page 0);
the o-proj + MLP epilogue runs once at the last (group, page) step.
Weight tiles use constant index maps, so the pipeline elides their
reloads across rows — and every projection matrix sits WHOLE in VMEM,
which bounds the widths this kernel can serve (see ROADMAP A3).

Two KV-append contracts (the caller owns the pool write):

- ``self_kv=True`` (fp pools): the kernel computes the current token's
  roped k/v IN-KERNEL, folds the token's self-attention term into the
  online-softmax init (pages then cover only the ``kv_len - 1`` cached
  positions), and RETURNS (k_cur, v_cur) for the caller to scatter into
  the pool after the launch. fp scatter+gather is lossless, so the
  in-register self term is bit-equal to a gather of the appended value.
- ``self_kv=False`` (int8 pools): the caller quantize-appends FIRST
  (the running-amax requant must be visible to the attention gather —
  an in-register fp self term would skip the quantization the cached
  token actually suffered) and the kernel attends over all ``kv_len``
  page positions.

rope inside the kernel avoids strided lane slicing (Mosaic-hostile) by
the pair-rotation-as-matmul identity: ``rope(x) = x * cos + (x @ SWAP)
* sin`` with ``SWAP[2i, 2i+1] = 1, SWAP[2i+1, 2i] = -1`` — one tiny MXU
dot instead of an interleaved de/re-shuffle. The per-row cos/sin phase
tables are precomputed outside (elementwise, XLA fuses them into the
operand stream).

Off-TPU callers get a pure-jnp fallback with identical math (dense
page gather + masked softmax, the ragged reference oracle's shape);
PADDLE_TPU_FORCE_PALLAS=1 runs the kernel body under the Pallas
interpreter — how CPU CI exercises it. The kv-head group split is
picked by the measured autotuner (kernels/autotune.py) under
PADDLE_TPU_AUTOTUNE=1, per shape key; under a trace only a cached
winner is consulted.

int4 weights (and any mixed layouts) take the jnp fallback: the packed
nibble unpack inside this kernel's prologue is not worth the Mosaic
surface until a chip run says otherwise.

Whole-model scope (:func:`fused_decode_model`): the decode LAYER LOOP
itself moves inside the traced program as a ``lax.scan`` over
LayerStack-stacked ``[L, ...]`` weights (:func:`stack_layer_params`)
and stacked per-layer KV pools/int8 scale columns. The scanned body is
the same fused layer body as above, so the whole decode step lowers to
ONE ``stablehlo.while`` whose body contains ONE layer-body site — one
launch per token instead of L, and under the on-device burst
``lax.while_loop`` one launch per burst (jit/hlo_forensics.py
``launch_stats`` holds the collapse). The caller still owns the pool
write, threaded through the scan as a callback: ``append_fn`` for fp
(scatter the returned k/v at the flat slot) and ``quant_append_fn``
for int8 (running-amax requant-append BEFORE attention — the
``self_kv=False`` contract above, per layer slice).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# row operands are blocked in eights: the second-minor dim of a TPU block
# must be a multiple of 8 (f32 sublanes) or the whole array
_ROW_BLOCK = 8

_LAYER_MATS = ("q", "k", "v", "o", "gate", "up", "down")

# process-wide record of a runtime Pallas failure that
# FLAGS_enable_fusion_fallback rerouted to the jnp body — what makes
# megakernel_mode() honest about the path that actually ran
_FALLBACK = {"tripped": False}


def megakernel_fallback_tripped() -> bool:
    """True once a Pallas launch failed at runtime and
    ``FLAGS_enable_fusion_fallback`` rerouted it to the jnp body."""
    return _FALLBACK["tripped"]


def reset_megakernel_fallback() -> None:
    """Clear the tripped-fallback record (tests; engine re-init)."""
    _FALLBACK["tripped"] = False


def megakernel_mode(layer=None, interpret=None) -> str:
    """How :func:`fused_decode_layer` would execute here: ``pallas``
    (TPU), ``interpret`` (forced Pallas interpreter), or ``jnp`` (the
    fallback body) — the bench artifact's ``megakernel_mode`` field.

    Pass a ``layer`` dict to report the mode ITS weights select:
    int4 / mixed quantized layouts take the jnp fallback on every
    backend, and reporting the environment's mode for them would
    fabricate a kernel that never runs. Pass ``interpret`` when the
    caller pinned :func:`fused_decode_layer`'s mode explicitly (the
    LLMEngine(interpret=...) knob) instead of leaving it env-driven.
    A runtime Pallas failure rerouted by
    ``FLAGS_enable_fusion_fallback`` IS knowable here: the reroute
    trips :func:`megakernel_fallback_tripped`, and while the flag keeps
    routing launches to the jnp body this reports ``jnp`` — the mode
    that actually runs, not the one that was selected."""
    if layer is not None and _weights_kernel_ready(layer) is None:
        return "jnp"
    if _FALLBACK["tripped"]:
        from ..core.flags import GLOBAL_FLAGS
        if GLOBAL_FLAGS.get("enable_fusion_fallback"):
            return "jnp"
    # an explicitly pinned interpret=True wins even on TPU — that is
    # what fused_decode_layer passes to pallas_call
    if interpret is True:
        return "interpret"
    from . import _on_tpu
    if _on_tpu():
        return "pallas"
    if interpret is None:
        interpret = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
    return "interpret" if interpret else "jnp"


def whole_weight_vmem_limit(operands) -> int:
    """Scoped-VMEM budget for a megakernel launch: its projection
    matrices sit WHOLE in VMEM, which the compiler's default 16 MiB
    scope holds only at toy widths. Ask for the operands plus working
    room, capped under the chip's 128 MiB so a layer that cannot fit is
    refused by the compiler and not by a guess made here."""
    need = sum(a.size * a.dtype.itemsize for a in operands) + (16 << 20)
    return int(min(max(need, 32 << 20), 100 << 20))


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _swap_matrix(dh):
    """Pair-rotation matmul operand: ``(x @ SWAP)[2i] = -x[2i+1]``,
    ``(x @ SWAP)[2i+1] = x[2i]`` — rope's rotated half without strided
    lane slicing."""
    r = jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (dh, dh), 1)
    even_r = (r % 2) == 0
    plus = (c == r + 1) & even_r
    minus = (c == r - 1) & ~even_r
    return plus.astype(jnp.float32) - minus.astype(jnp.float32)


def _rope_tables(kv_lens, theta, dh):
    """Interleaved-pair cos/sin phase tables for position
    ``kv_len - 1`` per row, expanded to full head_dim (pairs (2i, 2i+1)
    share frequency i)."""
    pos = jnp.maximum(kv_lens - 1, 0).astype(jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None] * inv                                # [R, dh/2]
    return (jnp.repeat(jnp.cos(ang), 2, axis=1),
            jnp.repeat(jnp.sin(ang), 2, axis=1))


def _weights_kernel_ready(layer):
    """fp arrays or all-int8 QuantizedWeight -> the kernel handles it;
    int4 / mixed layouts take the jnp fallback."""
    from ..quantization.low_bit import QuantizedWeight
    kinds = set()
    for k in _LAYER_MATS:
        w = layer[k]
        if isinstance(w, QuantizedWeight):
            if w.bits != 8:
                return None
            kinds.add("int8")
        else:
            kinds.add("fp")
    if len(kinds) != 1:
        return None
    return kinds.pop()


def _build_kernel(*, H, Hkv, grp, dh, ps, G, hb, self_kv, quant_w,
                  quant_kv, eps, scale):
    """One closure per (layout, shape) variant; refs are parsed off a
    computed layout because the quant/self_kv axes change the operand
    list."""

    def kernel(*refs):
        it = iter(refs)
        tbl_ref = next(it)
        kl_ref = next(it)
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

        wq = w_pair()
        wk = w_pair()
        wv = w_pair()
        wo = w_pair()
        wg = w_pair()
        wu = w_pair()
        wd = w_pair()
        kpg_ref = next(it)
        vpg_ref = next(it)
        hout_ref = next(it)
        kout_ref = vout_ref = None
        if self_kv:
            kout_ref = next(it)
            vout_ref = next(it)
        q_scr = next(it)
        m_scr = next(it)
        l_scr = next(it)
        acc_scr = next(it)

        ri = pl.program_id(1)
        r = pl.program_id(0) * _ROW_BLOCK + ri
        g = pl.program_id(2)
        p = pl.program_id(3)
        row = pl.ds(ri, 1)          # this step's row of the 8-row block
        kv_len = kl_ref[r]
        # cached positions visible in pages (self_kv keeps the current
        # token in-register, so pages cover one position fewer)
        Lc = kv_len - 1 if self_kv else kv_len

        def mat(pair, rows=slice(None)):
            w_ref, s_ref = pair
            w = w_ref[rows, :].astype(jnp.float32)
            if s_ref is not None:
                # int8 prologue dequant (int8_matmul's discipline): the
                # weight becomes fp only inside VMEM
                w = w * s_ref[...]
            return w

        def dot(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when((g == 0) & (p == 0))
        def _prologue():
            hv = h_ref[row, :]                              # [1, D] f32
            cosv = cos_ref[row, :]                          # [1, dh]
            sinv = sin_ref[row, :]
            swap = _swap_matrix(dh)
            x = _rms(hv, ln1_ref[...].astype(jnp.float32), eps)

            def rope(t):                                    # [1, dh]
                return t * cosv + dot(t, swap) * sinv

            # heads come off the flat projection by STATIC lane slices
            # in a static loop: Mosaic has no [1, H*dh] -> [H, dh]
            # shape cast when dh is under a full 128-lane tile
            qf = dot(x, mat(wq))                            # [1, H*dh]
            for hh in range(H):
                q_scr[pl.ds(hh, 1), :] = rope(
                    qf[:, hh * dh:(hh + 1) * dh])
            if self_kv:
                kf = dot(x, mat(wk))                        # [1, Hkv*dh]
                vf = dot(x, mat(wv))
                for j in range(Hkv):
                    kh = rope(kf[:, j * dh:(j + 1) * dh])
                    vh = vf[:, j * dh:(j + 1) * dh]
                    kout_ref[ri, pl.ds(j, 1), :] = kh
                    vout_ref[ri, pl.ds(j, 1), :] = vh
                    # the current token's self term seeds the online
                    # softmax of this kv head's grp query heads:
                    # m = s_self, l = exp(0) = 1, acc = v
                    heads = pl.ds(j * grp, grp)
                    m_scr[heads, :] = jnp.sum(
                        q_scr[heads, :] * kh, axis=1, keepdims=True) * scale
                    acc_scr[heads, :] = jnp.broadcast_to(vh, (grp, dh))
                l_scr[...] = jnp.ones_like(l_scr)
            else:
                m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
                l_scr[...] = jnp.zeros_like(l_scr)
                acc_scr[...] = jnp.zeros_like(acc_scr)

        base = p * ps

        @pl.when(base < Lc)
        def _page():
            kf = kpg_ref[...].reshape(hb, ps, dh).astype(jnp.float32)
            vf = vpg_ref[...].reshape(hb, ps, dh).astype(jnp.float32)
            if quant_kv:
                last_live = jnp.maximum(Lc - 1, 0) // ps
                page_id = tbl_ref[r, jnp.minimum(p, last_live)]
            for j in range(hb):                      # static head loop
                kj, vj = kf[j], vf[j]
                if quant_kv:
                    # per-(head, page) dequant scale off the prefetch
                    # channel (SMEM scalar read)
                    kj = kj * ks_ref[g * hb + j, page_id]
                    vj = vj * vs_ref[g * hb + j, page_id]
                row0 = (g * hb + j) * grp
                qj = q_scr[pl.ds(row0, grp), :]             # [grp, dh]
                s = jax.lax.dot_general(
                    qj, kj, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                posm = base + jax.lax.broadcasted_iota(
                    jnp.int32, (grp, ps), 1)
                s = jnp.where(posm < Lc, s, _NEG_INF)
                mj = m_scr[pl.ds(row0, grp), :]
                lj = l_scr[pl.ds(row0, grp), :]
                aj = acc_scr[pl.ds(row0, grp), :]
                m_cur = jnp.max(s, axis=1, keepdims=True)
                m_new = jnp.maximum(mj, m_cur)
                alpha = jnp.exp(mj - m_new)
                e = jnp.exp(s - m_new)
                l_scr[pl.ds(row0, grp), :] = \
                    lj * alpha + jnp.sum(e, axis=1, keepdims=True)
                m_scr[pl.ds(row0, grp), :] = m_new
                acc_scr[pl.ds(row0, grp), :] = aj * alpha + dot(e, vj)

        @pl.when((g == G - 1) & (p == pl.num_programs(3) - 1))
        def _epilogue():
            o = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)   # [H, dh]
            # o-proj without the [H, dh] -> [1, H*dh] shape cast: one
            # [1, dh] x [dh, D] dot per head over static row slices of
            # the o weight
            h2 = h_ref[row, :]
            for hh in range(H):
                h2 = h2 + dot(o[hh:hh + 1, :],
                              mat(wo, pl.ds(hh * dh, dh)))
            x2 = _rms(h2, ln2_ref[...].astype(jnp.float32), eps)
            mlp = dot(jax.nn.silu(dot(x2, mat(wg))) * dot(x2, mat(wu)),
                      mat(wd))
            hout_ref[row, :] = h2 + mlp

    return kernel


def _reference_layer(layer, h, k_pages, v_pages, block_tables, kv_lens, *,
                     eps, theta, num_heads, self_kv, k_scales, v_scales):
    """Pure-jnp fallback, math identical to the kernel (parity-tested):
    dense page gather + masked softmax, the ragged oracle's shape. The
    projections route through quantization.low_bit.matmul, so int8/int4
    serving weights work here too."""
    from ..models.generation import _rms_norm, _rope, _wmat
    R, _ = h.shape
    Hkv, _, ps, dh = k_pages.shape
    H = num_heads
    grp = H // Hkv
    scale = 1.0 / (dh ** 0.5)
    pos = jnp.maximum(kv_lens - 1, 0).astype(jnp.int32)
    x = _rms_norm(h[None], layer["ln1"], eps)[0]
    q = _rope(_wmat(x, layer["q"]).reshape(R, H, dh)[None],
              pos[None], theta, dh)[0]
    k_cur = v_cur = None
    if self_kv:
        k_cur = _rope(_wmat(x, layer["k"]).reshape(R, Hkv, dh)[None],
                      pos[None], theta, dh)[0]
        v_cur = _wmat(x, layer["v"]).reshape(R, Hkv, dh)
    Lc = kv_lens - (1 if self_kv else 0)
    K = k_pages[:, block_tables].astype(jnp.float32)  # [Hkv,R,PPS,ps,dh]
    V = v_pages[:, block_tables].astype(jnp.float32)
    if k_scales is not None:
        K = K * k_scales[:, block_tables, None, None]
        V = V * v_scales[:, block_tables, None, None]
    S = K.shape[2] * ps
    K = K.reshape(Hkv, R, S, dh)
    V = V.reshape(Hkv, R, S, dh)
    qh = q.reshape(R, Hkv, grp, dh).astype(jnp.float32)
    s = jnp.einsum("rhgd,hrsd->rhgs", qh, K) * scale
    posk = jnp.arange(S)
    s = jnp.where(posk[None, None, None, :] < Lc[:, None, None, None],
                  s, _NEG_INF)
    if self_kv:
        s_self = jnp.einsum(
            "rhgd,rhd->rhg", qh,
            jnp.asarray(k_cur, jnp.float32))[..., None] * scale
        s = jnp.concatenate([s, s_self], axis=-1)
        V = jnp.concatenate(
            [V, jnp.transpose(jnp.asarray(v_cur, jnp.float32),
                              (1, 0, 2))[:, :, None, :]], axis=2)
    w = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    o = jnp.einsum("rhgs,hrsd->rhgd", w, V).reshape(R, H * dh) \
        .astype(h.dtype)
    h2 = h + _wmat(o, layer["o"])
    x2 = _rms_norm(h2[None], layer["ln2"], eps)[0]
    mlp = _wmat(jax.nn.silu(_wmat(x2, layer["gate"]))
                * _wmat(x2, layer["up"]), layer["down"])
    return h2 + mlp, k_cur, v_cur


def _pick_groups(Hkv, key_dims, run_fn, traced):
    from .autotune import autotune_enabled, pick_cached
    default = {"head_groups": 1}
    if not autotune_enabled() or Hkv == 1:
        return default
    cands = [{"head_groups": g} for g in range(1, Hkv + 1) if Hkv % g == 0]
    return pick_cached(key=("decode_megakernel",) + tuple(key_dims),
                       requested=default, candidates=cands,
                       build_fn=lambda c: (lambda: run_fn(c)),
                       traced=traced)


def fused_decode_layer(layer, h, k_pages, v_pages, block_tables, kv_lens,
                       *, eps, theta, num_heads, self_kv=True,
                       interpret=None, k_scales=None, v_scales=None,
                       scope="layer", num_layers=1):
    """One fused decoder layer over q_len=1 rows.

    layer: dict with ln1/ln2 (fp) and q/k/v/o/gate/up/down projections
        (fp arrays or quantization.QuantizedWeight);
    h: [R, hidden] row hidden states; k_pages/v_pages:
        [Hkv, num_pages, page_size, dh]; block_tables: [R, PPS] int32;
    kv_lens: [R] int32 — the attention length per row INCLUDING the
        current token (its position is ``kv_len - 1``).
    self_kv=True: pages hold ``kv_len - 1`` cached tokens; the kernel
        computes the current token's k/v, attends it in-register, and
        returns them for the caller to append. self_kv=False: the
        caller appended first (the int8 running-amax contract); pages
        hold all ``kv_len`` tokens.
    scope/num_layers: autotune-cache provenance — ``"model"`` when the
        call sits inside :func:`fused_decode_model`'s scan over
        ``num_layers`` stacked layers. The scanned body competes for
        VMEM/pipeline slots differently than a standalone launch, so
        layer-scope and model-scope tunings must never share a cache
        line (kernels/autotune.py key separation).
    Returns ``(h_out, k_cur, v_cur)`` (k_cur/v_cur None when
    ``self_kv=False``).
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    forced = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
    from . import _on_tpu
    on_tpu = _on_tpu()
    if interpret is None:
        interpret = forced and not on_tpu
    kind = _weights_kernel_ready(layer)
    if not ((on_tpu or interpret) and kind is not None):
        return _reference_layer(
            layer, h, k_pages, v_pages, block_tables, kv_lens, eps=eps,
            theta=theta, num_heads=num_heads, self_kv=self_kv,
            k_scales=k_scales, v_scales=v_scales)

    quant_w = kind == "int8"
    quant_kv = k_scales is not None
    R, D = h.shape
    Hkv, _, ps, dh = k_pages.shape
    H = num_heads
    grp = H // Hkv
    PPS = block_tables.shape[1]
    scale = 1.0 / (dh ** 0.5)
    cos, sin = _rope_tables(kv_lens, theta, dh)
    # row operands ride f32 blocks of _ROW_BLOCK rows (the kernel upcast
    # them on entry and downcast on exit anyway, so moving the casts out
    # here is value-identical); pad rows read kv_len 0 and the null page
    Rp = -(-R // _ROW_BLOCK) * _ROW_BLOCK

    def pad_rows(a):
        return jnp.pad(a, ((0, Rp - R),) + ((0, 0),) * (a.ndim - 1))

    h_rows = pad_rows(h.astype(jnp.float32))
    cos, sin = pad_rows(cos), pad_rows(sin)
    tbl_rows, kl_rows = pad_rows(block_tables), pad_rows(kv_lens)
    # kv head dim of the current page block for the index maps below
    shift = 1 if self_kv else 0

    def kv_map_for(hb):
        def kv_map(rb, ri, g, p, tbl, kl, *rest):
            # dead pages clamp to the last live one: revisiting a block
            # lets the pipeline elide the copy (the ragged kernel trick)
            r = rb * _ROW_BLOCK + ri
            last = jnp.maximum(kl[r] - shift - 1, 0) // ps
            return (g, tbl[r, jnp.minimum(p, last)], 0, 0)
        return kv_map

    def row_map(rb, ri, g, p, *pf):
        return (rb, 0)

    def const_map(rb, ri, g, p, *pf):
        return (0, 0)

    def wop(key):
        """Weight operand(s) + spec(s) for one projection."""
        w = layer[key]
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
        kernel = _build_kernel(H=H, Hkv=Hkv, grp=grp, dh=dh, ps=ps, G=G,
                               hb=hb, self_kv=self_kv, quant_w=quant_w,
                               quant_kv=quant_kv, eps=float(eps),
                               scale=scale)
        operands = [h_rows, cos, sin,
                    jnp.asarray(layer["ln1"]).reshape(1, D),
                    jnp.asarray(layer["ln2"]).reshape(1, D)]
        in_specs = [pl.BlockSpec((_ROW_BLOCK, D), row_map),
                    pl.BlockSpec((_ROW_BLOCK, dh), row_map),
                    pl.BlockSpec((_ROW_BLOCK, dh), row_map),
                    pl.BlockSpec((1, D), const_map),
                    pl.BlockSpec((1, D), const_map)]
        for key in _LAYER_MATS:
            ops, specs = wop(key)
            operands += ops
            in_specs += specs
        operands += [k_pages, v_pages]
        in_specs += [pl.BlockSpec((hb, 1, ps, dh), kv_map_for(hb)),
                     pl.BlockSpec((hb, 1, ps, dh), kv_map_for(hb))]
        f32 = jnp.float32
        out_shape = [jax.ShapeDtypeStruct((Rp, D), f32)]
        out_specs = [pl.BlockSpec((_ROW_BLOCK, D), row_map)]
        if self_kv:
            out_shape += [jax.ShapeDtypeStruct((Rp, Hkv, dh), f32)] * 2
            out_specs += [pl.BlockSpec(
                (_ROW_BLOCK, Hkv, dh),
                lambda rb, ri, g, p, *pf: (rb, 0, 0))] * 2
        prefetch = [tbl_rows, kl_rows]
        if quant_kv:
            prefetch += [jnp.asarray(k_scales, jnp.float32),
                         jnp.asarray(v_scales, jnp.float32)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(Rp // _ROW_BLOCK, _ROW_BLOCK, G, PPS),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((H, dh), jnp.float32),    # roped queries
                pltpu.VMEM((H, 1), jnp.float32),     # m
                pltpu.VMEM((H, 1), jnp.float32),     # l
                pltpu.VMEM((H, dh), jnp.float32),    # acc
            ],
        )
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec, out_shape=out_shape,
            interpret=interpret, name="fused_decode_layer",
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=whole_weight_vmem_limit(
                    operands[:-2])),     # all but the HBM-paged pools
        )(*prefetch, *operands)
        return [o[:R].astype(h.dtype) for o in out]

    traced = any(isinstance(a, jax.core.Tracer)
                 for a in (h, k_pages, kv_lens))
    cfg = _pick_groups(
        Hkv, (R, D, H, Hkv, dh, PPS, ps, kind, bool(self_kv),
              bool(quant_kv), str(scope), int(num_layers)), run, traced)
    try:
        out = run(cfg)
    except Exception:
        from ..core.flags import GLOBAL_FLAGS
        if not GLOBAL_FLAGS.get("enable_fusion_fallback"):
            raise
        _FALLBACK["tripped"] = True
        from ..core.vlog import vlog
        vlog(0, "pallas decode megakernel failed; falling back to the "
                "jnp layer body (FLAGS_enable_fusion_fallback)")
        return _reference_layer(
            layer, h, k_pages, v_pages, block_tables, kv_lens, eps=eps,
            theta=theta, num_heads=num_heads, self_kv=self_kv,
            k_scales=k_scales, v_scales=v_scales)
    if self_kv:
        return tuple(out)
    return out[0], None, None


def stack_layer_params(layers):
    """Stack a list of per-layer param pytrees into one ``[L, ...]``
    tree — the LayerStack layout :func:`fused_decode_model` scans over.

    Works uniformly over fp dicts, registered
    ``quantization.QuantizedWeight`` pytrees (qdata/scale leaves stack;
    bits/rows aux must match across layers) and LoRA adapter slabs,
    because it is a plain leafwise ``jnp.stack``: a scan slice of the
    result is bit-equal to the original per-layer tree.
    """
    layers = list(layers)
    if not layers:
        raise ValueError("stack_layer_params needs at least one layer")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def fused_decode_model(layers, h, k_pages, v_pages, block_tables,
                       kv_lens, *, eps, theta, num_heads, self_kv=True,
                       interpret=None, k_scales=None, v_scales=None,
                       append_fn=None, quant_append_fn=None):
    """Whole-model decode step: ``lax.scan`` of the fused layer body
    over stacked ``[L, ...]`` weights and KV pools — ONE layer-body
    site in the lowered program, so one launch per token (and, under
    the caller's burst ``lax.while_loop``, per burst).

    layers: stacked param tree from :func:`stack_layer_params` (leaves
        ``[L, ...]``); k_pages/v_pages: ``[L, Hkv, num_pages, ps, dh]``
        stacked pools; k_scales/v_scales: ``[L, Hkv, num_pages]``
        stacked int8 scale columns (``self_kv=False`` only);
    block_tables/kv_lens: as :func:`fused_decode_layer` (shared across
        layers — every layer of a request lives at the same slots).
    append_fn(Kp, Vp, k_cur, v_cur) -> (Kp, Vp): fp pool write for one
        layer slice, run INSIDE the scan after the kernel returns the
        current token's k/v (``self_kv=True``). quant_append_fn(Kp, Ks,
        Vp, Vs, k_cur, v_cur) -> (Kp, Ks, Vp, Vs): int8 running-amax
        requant-append for one layer slice, run BEFORE the kernel
        (``self_kv=False`` — the append must be visible to the gather).
        The caller owns both (NULL-page masking, slot layout), so the
        scanned body replays the layer-scope pool writes bit-for-bit.

    Returns ``(h_out, k_pages, v_pages, k_scales, v_scales)`` with the
    updated stacked pools (scales None in the fp contract).
    """
    num_layers = int(k_pages.shape[0])
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)

    def _layer(lyr, hc, Kp, Vp, Ks=None, Vs=None):
        return fused_decode_layer(
            lyr, hc, Kp, Vp, block_tables, kv_lens, eps=eps, theta=theta,
            num_heads=num_heads, self_kv=self_kv, interpret=interpret,
            k_scales=Ks, v_scales=Vs, scope="model",
            num_layers=num_layers)

    if self_kv:
        if append_fn is None:
            raise ValueError("self_kv=True needs append_fn (the caller "
                             "owns the fp pool scatter)")
        if k_scales is not None or v_scales is not None:
            raise ValueError("self_kv=True is the fp contract; int8 "
                             "scale columns need self_kv=False")

        def body(hc, xs):
            lyr, Kp, Vp = xs
            h2, k_cur, v_cur = _layer(lyr, hc, Kp, Vp)
            Kp, Vp = append_fn(Kp, Vp, k_cur, v_cur)
            return h2, (Kp, Vp)

        h_out, (Kps, Vps) = jax.lax.scan(body, h, (layers, k_pages,
                                                   v_pages))
        return h_out, Kps, Vps, None, None

    if quant_append_fn is None:
        raise ValueError("self_kv=False needs quant_append_fn (the "
                         "caller owns the running-amax append)")
    if k_scales is None or v_scales is None:
        raise ValueError("self_kv=False needs stacked k_scales/v_scales")
    from ..models.generation import _rms_norm, _rope, _wmat
    R = h.shape[0]
    Hkv, dh = int(k_pages.shape[1]), int(k_pages.shape[4])
    pos = jnp.maximum(kv_lens - 1, 0)

    def body(hc, xs):
        lyr, Kp, Vp, Ks, Vs = xs
        # pre-append prologue, identical math to the layer-scope int8
        # path: the current token's k/v must be requant-appended before
        # the kernel's gather sees the pool
        x = _rms_norm(hc[None], lyr["ln1"], eps)[0]
        k_cur = _rope(_wmat(x, lyr["k"]).reshape(R, Hkv, dh)[None],
                      pos[None], theta, dh)[0]
        v_cur = _wmat(x, lyr["v"]).reshape(R, Hkv, dh)
        Kp, Ks, Vp, Vs = quant_append_fn(Kp, Ks, Vp, Vs, k_cur, v_cur)
        h2, _, _ = _layer(lyr, hc, Kp, Vp, Ks, Vs)
        return h2, (Kp, Vp, Ks, Vs)

    h_out, (Kps, Vps, Kss, Vss) = jax.lax.scan(
        body, h, (layers, k_pages, v_pages, k_scales, v_scales))
    return h_out, Kps, Vps, Kss, Vss


__all__ = ["fused_decode_layer", "fused_decode_model",
           "stack_layer_params", "megakernel_mode",
           "megakernel_fallback_tripped", "reset_megakernel_fallback"]
