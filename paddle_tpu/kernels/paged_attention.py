"""Ragged paged attention (Pallas TPU) — one kernel for any traffic mix.

Reference capability being matched: paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu (paged KV with per-sequence block
tables, variable sequence lengths, GQA) — rewritten in the shape of
"Ragged Paged Attention" (arxiv 2604.15464): instead of one executable
per (batch, pages) decode bucket plus a prefill ladder, a SINGLE kernel
takes queries packed row-wise into one ``[total_q_tokens, ...]`` buffer
with scalar-prefetched per-sequence ``(q_start, q_len, kv_len)``
metadata, so a mixed batch of decode steps (q_len=1) and prefill chunks
(q_len=k, causally masked inside the kernel) runs as ONE grid:

- the KV pool stays paged ``[num_kv_heads, num_pages, page_size,
  head_dim]`` (head-major so one grid step DMAs exactly one head's page);
- ``block_tables [num_seqs, pages_per_seq]`` maps each sequence's logical
  pages to pool pages — scalar-prefetched so the index map can steer the
  DMA before the kernel body runs;
- queries are packed into fixed ``q_block``-row slots (each sequence's
  rows start at a multiple of ``q_block``), and a ``block_row`` map
  (derived in-graph from the sorted ``q_starts``) assigns each q block to
  its sequence. Grid = (q_block index, kv_head, page): the page axis
  iterates sequentially, so VMEM scratch carries the online-softmax state
  (m, l, acc) across pages — only pages up to the block's causal horizon
  are read, which is the entire point of paged attention (HBM reads scale
  with true kv length, not pool capacity);
- causal masking is per q token INSIDE the kernel: token ``i`` of a
  chunk at absolute position ``kv_len - q_len + i`` sees kv positions
  ``<=`` that — decode (q_len=1) degenerates to the old ``pos < seq_len``
  mask, so one program covers prefill chunks and decode rows alike.

GQA: each q block's ``[q_block * group, head_dim]`` rows ride one MXU
matmul per page; decode rows waste ``q_block - 1`` of those rows to
padding, which is free in practice — the MXU tile is 128 rows and decode
is bandwidth-bound on the page DMAs, which are unchanged.

int8 pools (``k_scales``/``v_scales`` per (head, page)) dequantize the
DMA'd page in-kernel with scales read off the scalar-prefetch channel
(SMEM) — the low-bit KV path rides the ragged kernel unchanged.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _ragged_kernel(row_ref, qs_ref, ql_ref, kl_ref, tbl_ref,
                   q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   page_size, q_block, scale, ks_ref=None, vs_ref=None):
    g = pl.program_id(0)          # q block
    h = pl.program_id(1)          # kv head
    p = pl.program_id(2)          # logical page of this block's sequence

    row = row_ref[g]
    q_len = ql_ref[row]
    kv_len = kl_ref[row]
    kv_start = kv_len - q_len     # absolute position of the chunk's token 0
    blk_off = g * q_block - qs_ref[row]   # this block's offset in the chunk

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    base = p * page_size
    # causal horizon of the block's LAST live token: pages past it hold
    # nothing any of this block's queries may see — skip them entirely
    # (early prefill chunks therefore read only their causal prefix)
    horizon = jnp.minimum(kv_len, kv_start + blk_off + q_block)
    live_block = (blk_off >= 0) & (blk_off < q_len)

    @pl.when(live_block & (base < horizon))
    def _page():
        qb, _, grp, d = q_ref.shape
        q = q_ref[...].reshape(qb * grp, d).astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)        # [ps, d]
        v = v_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            # int8 pool: dequantize the DMA'd page with its own
            # per-(head, page) scale — a scalar read off the prefetch
            # channel (SMEM), indexed by the same pool page the DMA read
            last_live = jnp.maximum(kv_len - 1, 0) // page_size
            page = tbl_ref[row, jnp.minimum(p, last_live)]
            k = k * ks_ref[h, page]
            v = v * vs_ref[h, page]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [qb*grp, ps]
        # per-token causal mask: token i of the chunk (absolute position
        # kv_start + blk_off + i) sees kv positions <= its own; tokens
        # past q_len (slot padding) are masked out entirely
        s3 = s.reshape(qb, grp, page_size)
        tok = blk_off + jax.lax.broadcasted_iota(jnp.int32, s3.shape, 0)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s3.shape, 2)
        ok = (tok < q_len) & (pos <= kv_start + tok) & (pos < kv_len)
        s = jnp.where(ok, s3, _NEG_INF).reshape(qb * grp, page_size)
        m_prev = m_ref[...]                        # [qb*grp, 1]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new)                     # [qb*grp, ps]
        l_ref[...] = l_prev * alpha + jnp.sum(e, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            e, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [qb*grp, d]

    @pl.when(p == pl.num_programs(2) - 1)
    def _fin():
        qb, _, grp, d = o_ref.shape
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)) \
            .reshape(qb, 1, grp, d).astype(o_ref.dtype)


def _ragged_kernel_quant(row_ref, qs_ref, ql_ref, kl_ref, tbl_ref, ks_ref,
                         vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                         acc_ref, *, page_size, q_block, scale):
    """int8-pool variant: the per-(head, page) dequant scales ride the
    scalar-prefetch channel (SMEM) as operands 5 and 6."""
    _ragged_kernel(row_ref, qs_ref, ql_ref, kl_ref, tbl_ref, q_ref, k_ref,
                   v_ref, o_ref, m_ref, l_ref, acc_ref,
                   page_size=page_size, q_block=q_block, scale=scale,
                   ks_ref=ks_ref, vs_ref=vs_ref)


def ragged_block_row(q_starts, num_blocks, q_block):
    """The q-block -> sequence map the ragged kernel steers its DMAs by:
    derived from the ascending slot starts; blocks past every live slot
    resolve to the last row (their tokens mask dead in-kernel). Exposed
    so a fused prefill step can compute it ONCE per step and share it
    across every layer's attention call (kernels/prefill_megakernel.py)
    — the ops are identical to the in-call derivation, so passing the
    result back through ``block_row=`` is bitwise-neutral."""
    q_starts = q_starts.astype(jnp.int32)
    row = (jnp.searchsorted(
        q_starts, jnp.arange(num_blocks, dtype=jnp.int32) * q_block,
        side="right") - 1).astype(jnp.int32)
    return jnp.maximum(row, 0)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, q_starts,
                           q_lens, kv_lens, *, q_block=8, scale=None,
                           interpret=False, k_scales=None, v_scales=None,
                           block_row=None):
    """Mixed prefill-chunk + decode attention over a paged KV cache.

    q:            [total_q_tokens, num_q_heads, head_dim] — queries of
        every sequence packed row-wise. Each sequence's rows occupy one
        contiguous slot starting at ``q_starts[i]`` (a multiple of
        ``q_block``); rows past ``q_lens[i]`` inside a slot are padding.
    k_pages/v_pages: [num_kv_heads, num_pages, page_size, head_dim]
    block_tables: [num_seqs, pages_per_seq] int32 pool-page ids
    q_starts:     [num_seqs] int32, ascending; rows with no queries this
        launch (padding rows) carry ``q_start = total_q_tokens, q_len=0``
    q_lens:       [num_seqs] int32 — 1 for decode rows, k for a prefill
        chunk of k tokens (causally masked in-kernel)
    kv_lens:      [num_seqs] int32 valid KV length per sequence AFTER the
        chunk's tokens were appended (so ``kv_len - q_len`` is the
        absolute position of the chunk's first token)
    k_scales/v_scales: [num_kv_heads, num_pages] fp32 per-(head, page)
        dequant scales for int8 pools (both or neither).
    block_row:    optional precomputed :func:`ragged_block_row` result
        (``[total_q_tokens // q_block] int32``) — lets a fused prefill
        step derive the map once and share it across layers.
    Returns [total_q_tokens, num_q_heads, head_dim]; padding rows hold
    garbage (finite, never NaN) and must be ignored by the caller.
    """
    t, hq, d = q.shape
    hkv, _, page_size, dk = k_pages.shape
    if dk != d:
        raise ValueError(f"head_dim mismatch: q {d} vs pages {dk}")
    if hq % hkv != 0:
        raise ValueError(f"num_q_heads {hq} not a multiple of kv heads {hkv}")
    if t % q_block != 0:
        raise ValueError(f"total_q_tokens {t} not a multiple of q_block "
                         f"{q_block}")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    group = hq // hkv
    pages_per_seq = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    quantized = k_scales is not None
    num_blocks = t // q_block

    q_starts = q_starts.astype(jnp.int32)
    if block_row is None:
        # q block -> sequence map, derived from the (ascending) slot
        # starts; blocks past every live slot resolve to the last row
        # and mask dead
        block_row = ragged_block_row(q_starts, num_blocks, q_block)
    else:
        block_row = jnp.asarray(block_row, jnp.int32)

    qg = q.reshape(t, hkv, group, d)

    def _kv_map(g, h, p, rows, qs, ql, kl, tbl, *scales):
        # dead pages (past the sequence's last live page) clamp to the
        # last live page: revisiting the same block lets the pipeline
        # elide the copy, so HBM reads scale with true kv_len
        row = rows[g]
        last_live = jnp.maximum(kl[row] - 1, 0) // page_size
        return (h, tbl[row, jnp.minimum(p, last_live)], 0, 0)

    def _q_map(g, h, p, rows, qs, ql, kl, tbl, *scales):
        return (g, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_row, q_starts, q_lens, kv_lens, block_tables
        # (+ k/v scales for int8 pools)
        num_scalar_prefetch=7 if quantized else 5,
        grid=(num_blocks, hkv, pages_per_seq),
        in_specs=[
            pl.BlockSpec((q_block, 1, group, d), _q_map),
            pl.BlockSpec((1, 1, page_size, d), _kv_map),
            pl.BlockSpec((1, 1, page_size, d), _kv_map),
        ],
        out_specs=pl.BlockSpec((q_block, 1, group, d), _q_map),
        scratch_shapes=[
            pltpu.VMEM((q_block * group, 1), jnp.float32),   # m
            pltpu.VMEM((q_block * group, 1), jnp.float32),   # l
            pltpu.VMEM((q_block * group, d), jnp.float32),   # acc
        ],
    )
    prefetch = [block_row, q_starts,
                q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32),
                block_tables.astype(jnp.int32)]
    kernel = _ragged_kernel
    if quantized:
        prefetch += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]
        kernel = _ragged_kernel_quant
    out = pl.pallas_call(
        functools.partial(kernel, page_size=page_size, q_block=q_block,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((t, hkv, group, d), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret, name="ragged_paged_attention",
    )(*prefetch, qg, k_pages, v_pages)
    return out.reshape(t, hq, d)


def paged_attention(q, k_pages, v_pages, block_tables, seq_lens, *,
                    scale=None, interpret=False, k_scales=None,
                    v_scales=None):
    """Single-token decode attention over a paged KV cache — the
    ``q_len = 1`` special case of :func:`ragged_paged_attention` (one
    query row per sequence, ``q_block = 1``). Kept as the API the dense
    Generator's paged mode and older tests drive.

    q:            [batch, num_q_heads, head_dim]
    k_pages/v_pages: [num_kv_heads, num_pages, page_size, head_dim]
    block_tables: [batch, pages_per_seq] int32 pool-page ids
    seq_lens:     [batch] int32 valid KV length per sequence
    Returns [batch, num_q_heads, head_dim].
    """
    b = q.shape[0]
    arange = jnp.arange(b, dtype=jnp.int32)
    return ragged_paged_attention(
        q, k_pages, v_pages, block_tables,
        q_starts=arange, q_lens=jnp.ones((b,), jnp.int32),
        kv_lens=seq_lens.astype(jnp.int32), q_block=1, scale=scale,
        interpret=interpret, k_scales=k_scales, v_scales=v_scales)


def paged_attention_reference(q, k_pages, v_pages, block_tables, seq_lens,
                              scale=None, k_scales=None, v_scales=None):
    """jnp oracle: gather each sequence's pages densely, masked softmax.
    int8 pools dequantize at the gather with the per-(head, page) scales."""
    b, hq, d = q.shape
    hkv, _, ps, _ = k_pages.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    outs = []
    for i in range(b):
        tbl = block_tables[i]                     # [pages_per_seq]
        k = k_pages[:, tbl].astype(jnp.float32)   # [hkv, pps, ps, d]
        v = v_pages[:, tbl].astype(jnp.float32)
        if k_scales is not None:
            k = k * k_scales[:, tbl, None, None]
            v = v * v_scales[:, tbl, None, None]
        k = k.reshape(hkv, -1, d)                 # [hkv, S, d]
        v = v.reshape(hkv, -1, d)
        qi = q[i].reshape(hkv, group, d)
        s = jnp.einsum("hgd,hsd->hgs", qi, k) * scale
        pos = jnp.arange(s.shape[-1])
        s = jnp.where(pos[None, None, :] < seq_lens[i], s, _NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hgs,hsd->hgd", w, v).reshape(hq, d))
    return jnp.stack(outs)


def ragged_paged_attention_reference(q, k_pages, v_pages, block_tables,
                                     q_starts, q_lens, kv_lens, scale=None,
                                     k_scales=None, v_scales=None):
    """jnp oracle for the ragged kernel: per sequence, gather its pages
    densely and run a causally-masked softmax over its chunk's queries;
    rows outside any live slot stay zero."""
    t, hq, d = q.shape
    hkv, _, ps, _ = k_pages.shape
    group = hq // hkv
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    out = np.zeros((t, hq, d), np.float32)
    q_starts = np.asarray(q_starts)
    q_lens = np.asarray(q_lens)
    kv_lens = np.asarray(kv_lens)
    for i in range(len(q_lens)):
        ql, kl = int(q_lens[i]), int(kv_lens[i])
        if ql == 0:
            continue
        qs = int(q_starts[i])
        tbl = block_tables[i]
        k = k_pages[:, tbl].astype(jnp.float32)
        v = v_pages[:, tbl].astype(jnp.float32)
        if k_scales is not None:
            k = k * k_scales[:, tbl, None, None]
            v = v * v_scales[:, tbl, None, None]
        k = k.reshape(hkv, -1, d)
        v = v.reshape(hkv, -1, d)
        qi = q[qs:qs + ql].reshape(ql, hkv, group, d)
        s = jnp.einsum("qhgd,hsd->hgqs", qi, k) * scale
        pos = np.arange(s.shape[-1])
        # token j of the chunk sits at absolute position kl - ql + j
        limit = (kl - ql + np.arange(ql))[None, None, :, None]
        ok = (pos[None, None, None, :] <= limit) & \
            (pos[None, None, None, :] < kl)
        s = jnp.where(jnp.asarray(ok), s, _NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqs,hsd->qhgd", w, v).reshape(ql, hq, d)
        out[qs:qs + ql] = np.asarray(o)
    return jnp.asarray(out)


__all__ = ["paged_attention", "paged_attention_reference",
           "ragged_block_row", "ragged_paged_attention",
           "ragged_paged_attention_reference"]
