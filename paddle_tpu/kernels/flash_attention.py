"""Flash attention as a Pallas TPU kernel (forward + backward).

TPU-native rebuild of the reference's flash-attention CUDA kernels
(paddle/phi/kernels/gpu/flash_attn_kernel.cu, flash_attn_grad_kernel.cu —
which wrap the upstream flash-attn library). Design follows the online-
softmax tiling of Dao et al.: the [s_q, s_k] score matrix lives only as
[block_q, block_k] tiles in VMEM; running max/denominator are carried in
f32 scratch across the innermost (k-block) grid dimension, which TPU
Pallas iterates sequentially per core.

Layout: [batch, heads, seq, head_dim] (kernel layout; the nn.functional
surface transposes from paddle's [b, s, h, d]). GQA is handled by mapping
query head h to kv head h // (hq // hkv) in the k/v index maps.

Backward uses the standard two-kernel split with recomputation:
``dq`` accumulates over k blocks; ``dk/dv`` accumulates over q blocks; the
softmax statistics are re-derived from the saved logsumexp, so nothing
quadratic is ever stored.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30  # large-negative instead of -inf: avoids NaN from inf-inf


def _causal_mask(iq, ik, block_q, block_k, offset):
    """Boolean [block_q, block_k] mask: query may attend to key if
    q_pos + offset >= k_pos (offset = s_k - s_q aligns sequence ends)."""
    q_ids = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return (iq * block_q + q_ids + offset) >= (ik * block_k + k_ids)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, nk, offset):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: a k block contributes iff its first key is visible to the
    # last query of the q block.
    run = True
    if causal:
        run = ik * block_k <= (iq + 1) * block_q - 1 + offset

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                       # [block_q, d]
        k = k_ref[0, 0]                       # [block_k, d]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k, offset),
                          s, NEG_INF)
        m_prev = m_scr[:]                     # [bq, 128]
        m_cur = jnp.max(s, axis=1, keepdims=True)        # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)    # broadcast -> [bq, 128]
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # [bq, 1]
        p = jnp.exp(s - m_new[:, :1])                    # [bq, bk]
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)       # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:, :1] + jnp.log(l)).reshape(1, block_q)


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret):
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    nq, nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    offset = sk - sq

    grid = (b, hq, nq, nk)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, iq, ik: (bi, hi // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, iq, ik: (bi, hi // group, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, iq, ik: (bi, hi, 0, iq)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret, name="flash_attention_fwd",
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hq * sq * sk * d // (2 if causal else 1),
            bytes_accessed=(q.size + k.size + v.size + q.size) * q.dtype.itemsize,
            transcendentals=b * hq * sq * sk),
    )(q, k, v)
    return out, lse.reshape(b, hq, sq)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale, causal, block_q, block_k, nk, offset):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = ik * block_k <= (iq + 1) * block_q - 1 + offset

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(block_q, 1)
        delta = delta_ref[0, 0].reshape(block_q, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k, offset),
                          s, NEG_INF)
        p = jnp.exp(s - lse)                                    # [bq, bk]
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                                   # [bq, bk]
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, block_q, block_k, nq, offset):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        # q block contributes iff its last query can see the first key.
        run = (iq + 1) * block_q - 1 + offset >= ik * block_k

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].reshape(block_q, 1)
        delta = delta_ref[0, 0].reshape(block_q, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_causal_mask(iq, ik, block_q, block_k, offset),
                          s, NEG_INF)
        p = jnp.exp(s - lse)                                    # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                 # [bk, d]
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [bk, d]

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                    # [b, hq, sq]
    return _bwd_impl(q, k, v, do, lse, delta, scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, interpret=interpret)


def _bwd_impl(q, k, v, do, lse, delta, *, scale, causal, block_q, block_k,
              interpret):
    """Flash backward given saved softmax stats (also the per-block engine
    of ring attention, where ``lse`` is the globally-combined logsumexp)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    group = hq // hkv
    block_q, block_k = min(block_q, sq), min(block_k, sk)
    nq, nk = pl.cdiv(sq, block_q), pl.cdiv(sk, block_k)
    offset = sk - sq

    lse_r = lse.reshape(b, hq, 1, sq)
    delta_r = delta.reshape(b, hq, 1, sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          offset=offset),
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, iq, ik: (bi, hi // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, iq, ik: (bi, hi // group, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, iq, ik: (bi, hi, 0, iq)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, iq, ik: (bi, hi, 0, iq)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, iq, ik: (bi, hi, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret, name="flash_attention_bwd_dq",
    )(q, k, v, do, lse_r, delta_r)

    # dk/dv are accumulated per *query* head then reduced over the GQA
    # group outside the kernel (cheap: [b, hq, sk, d] -> [b, hkv, sk, d]).
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          offset=offset),
        grid=(b, hq, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ik, iq: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ik, iq: (bi, hi // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ik, iq: (bi, hi // group, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ik, iq: (bi, hi, iq, 0)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, ik, iq: (bi, hi, 0, iq)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda bi, hi, ik, iq: (bi, hi, 0, iq)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ik, iq: (bi, hi, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ik, iq: (bi, hi, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hq, sk, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret, name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse_r, delta_r)

    if group > 1:
        dk = dk.reshape(b, hkv, group, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv.reshape(b, hkv, group, sk, d).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, interpret=interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    return _bwd(scale, causal, block_q, block_k, interpret, res, g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                             interpret=False):
    """Forward-only flash attention returning (out, logsumexp [b, h, s]).

    The block-level engine of ring attention (distributed/context_parallel);
    not differentiable by itself — ring attention defines its own VJP over
    the combined statistics.
    """
    b, hq, sq, d = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _fwd(q, k, v, scale=float(scale), causal=bool(causal),
                block_q=min(block_q, sq), block_k=min(block_k, sk),
                interpret=bool(interpret))


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """Flash attention over [batch, heads, seq, head_dim] arrays.

    Differentiable (custom VJP with Pallas backward kernels). Supports GQA
    (hq a multiple of hkv) and unequal q/k lengths (sequence ends aligned,
    as in causal decode). seq lengths must be multiples of the block sizes.
    """
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, f"GQA needs hq % hkv == 0, got {hq}, {hkv}"
    from .autotune import autotune_enabled, pick_cached
    if autotune_enabled():
        # runtime block-size selection with a per-shape winner cache
        # (reference: phi/kernels/autotune switch_autotune.h + cache.h)
        cands = [{"block_q": bq, "block_k": bk}
                 for bq in sorted({min(b, sq) for b in (128, 256, 512)})
                 for bk in sorted({min(b, sk) for b in (128, 256, 512)})
                 if sq % bq == 0 and sk % bk == 0]
        # the caller's explicit (valid) blocks always compete, so enabling
        # autotune can never break or silently override a working call
        explicit = {"block_q": min(block_q, sq), "block_k": min(block_k, sk)}
        if not (sq % explicit["block_q"] == 0
                and sk % explicit["block_k"] == 0) and cands:
            explicit = cands[0]
        cfg = pick_cached(
            key=("flash_attention", tuple(q.shape), tuple(k.shape),
                 str(q.dtype), bool(causal), bool(interpret)),
            requested=explicit,
            candidates=cands,
            build_fn=lambda c: (lambda: _flash(
                q, k, v, float(scale or 1.0 / math.sqrt(d)), bool(causal),
                int(min(c["block_q"], sq)), int(min(c["block_k"], sk)),
                bool(interpret))),
            traced=isinstance(q, jax.core.Tracer))
        block_q, block_k = cfg["block_q"], cfg["block_k"]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (
        f"seq lens ({sq}, {sk}) must be multiples of blocks "
        f"({block_q}, {block_k})")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return _flash(q, k, v, float(scale), bool(causal), int(block_q),
                  int(block_k), bool(interpret))
