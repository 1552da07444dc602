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
  head_dim]`` and stays in HBM (``memory_space=pl.ANY``): the body
  fetches the pages it walks itself, one strided DMA a page bringing
  every kv head of it;
- ``block_tables [num_seqs, pages_per_seq]`` maps each sequence's logical
  pages to pool pages — scalar-prefetched (SMEM) so the body can steer
  its DMAs by it;
- queries are packed into fixed ``q_block``-row slots (each sequence's
  rows start at a multiple of ``q_block``), and a ``block_row`` map
  (derived in-graph from the sorted ``q_starts``) assigns each q block to
  its sequence. Grid = (q_block index,), and the walk over KV is a LOOP
  inside the body whose trip count follows what is live: slabs of
  ``ragged_slab_pages`` pages (256 KV tokens) from 0 to the block's
  causal horizon, none for a block outside every live slot. The slab's
  page copies are double-buffered (slab ``i+1`` flies while slab ``i`` is
  multiplied), pages of the last slab past the horizon are not fetched,
  and VMEM scratch carries each head's online-softmax state (m, l, acc)
  across the loop. So HBM reads AND time scale with live KV: a decode
  row at 400 tokens costs 2 iterations. (Before PR 27 the page axis was a
  grid axis, ``max_len / page_size`` steps a q block a head whatever was
  live: dead steps skipped their arithmetic and their DMA, so the reads
  scaled with live KV, but each was still walked, and the time did not —
  40,960 grid steps a layer at 7B widths, 0.3 % of the HBM roofline);
- causal masking is per q token INSIDE the kernel: token ``i`` of a
  chunk at absolute position ``kv_len - q_len + i`` sees kv positions
  ``<=`` that — decode (q_len=1) degenerates to the old ``pos < seq_len``
  mask, so one program covers prefill chunks and decode rows alike. V
  rows past the horizon are zeroed before the weighted sum: their weight
  is exactly 0, and what lies there (the tail of a last page, a slab
  page that was not fetched) may be anything.

A window layer (``window=W``, static: a query at position ``p`` sees
keys ``p - W < j <= p``) is the same program with a lower bound: the
walk starts at the slab that holds the first key the block's FIRST
query may see, pages under that key's are not fetched (the pool has
released them: ``serving/kv_cache.py``'s window group), and the mask
gains ``j > p - W``. ``window=None`` lowers what it lowered before.

GQA: each q block's ``[q_block * group, head_dim]`` rows ride one MXU
matmul per slab and head; decode rows waste ``q_block - 1`` of those
rows to padding, which is free in practice — the MXU tile is 128 rows.
A chunk's prefix is walked once a q block (``ragged_kv_tokens_read``
counts it): a q tile as wide as the chunk is the follow-on.

int8 pools (``k_scales``/``v_scales`` per (head, page)) dequantize each
fetched page in-kernel with scales read off the scalar-prefetch channel
(SMEM) by pool page — the low-bit KV path rides the ragged kernel
unchanged. A head narrower than the 128 lanes is zero-padded to them by
the wrapper (the chip's compiler slices an HBM ref only by whole lane
rows).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_SLAB_TOKENS = 256


def ragged_slab_pages(page_size, pages_per_seq):
    """Pages one fetch of the ragged kernel brings into VMEM: a slab of
    ``_SLAB_TOKENS`` KV tokens, so a q block's scores against it fill
    whole lane tiles (a page larger than that is its own slab; a block
    table shorter than that is one slab)."""
    return min(max(1, _SLAB_TOKENS // page_size), pages_per_seq)


def ragged_kv_tokens_read(q_lens, kv_lens, *, q_block, page_size,
                          pages_per_seq, window=None):
    """KV tokens ONE kv head's walk covers for a launch with these
    (numpy) row lengths: over the live q blocks, each block's causal
    horizon rounded up to the slab, less (with a ``window``) the whole
    slabs below the first key the block's first query may see. The
    roofline's floor counts a live token once; this counts it once a q
    block that sees it (a 64-token chunk at ``q_block`` 8 walks its
    prefix 8 times)."""
    q_lens = np.asarray(q_lens, np.int64)
    kv_lens = np.asarray(kv_lens, np.int64)
    slab = ragged_slab_pages(page_size, pages_per_seq) * page_size
    blocks = -(-q_lens // q_block)               # live q blocks a row
    row = np.repeat(np.arange(len(q_lens)), blocks)
    first = np.cumsum(blocks) - blocks           # a row's first block
    off = (np.arange(len(row)) - first[row]) * q_block
    start = kv_lens[row] - q_lens[row] + off     # the block's first query
    horizon = np.minimum(kv_lens[row], start + q_block)
    walked = -(-horizon // slab)
    if window is not None:
        walked = walked - np.maximum(start - window + 1, 0) // slab
    return int((walked * slab).sum())


def _ragged_kernel(row_ref, qs_ref, ql_ref, kl_ref, tbl_ref, *refs,
                   page_size, q_block, scale, quantized, window):
    ks_ref = vs_ref = None
    if quantized:
        # int8 pool: the per-(head, page) dequant scales ride the
        # scalar-prefetch channel (SMEM) as operands 5 and 6
        ks_ref, vs_ref, *refs = refs
    (q_ref, k_hbm, v_hbm, o_ref,
     k_buf, v_buf, sem, m_ref, l_ref, acc_ref) = refs
    g = pl.program_id(0)          # q block

    row = row_ref[g]
    q_len = ql_ref[row]
    kv_len = kl_ref[row]
    kv_start = kv_len - q_len     # absolute position of the chunk's token 0
    blk_off = g * q_block - qs_ref[row]   # this block's offset in the chunk

    qb, hkv, grp, d = q_ref.shape
    ppf = k_buf.shape[1]          # pages a fetch
    slab = ppf * page_size        # KV tokens a fetch

    # causal horizon of the block's LAST live token: KV past it holds
    # nothing any of this block's queries may see, so the walk ends
    # there (early prefill chunks read only their causal prefix); a
    # block outside every live slot walks nothing
    live_block = (blk_off >= 0) & (blk_off < q_len)
    horizon = jnp.where(
        live_block, jnp.minimum(kv_len, kv_start + blk_off + q_block), 0)
    n_pages = pl.cdiv(horizon, page_size)
    n_slabs = pl.cdiv(horizon, slab)
    if window is None:
        first_key = first_page = first_slab = 0
    else:
        # a window layer: a query at position p sees keys j with
        # p - window < j <= p, so nothing below the block's FIRST
        # query's window is seen by any of its queries. The walk starts
        # at the slab that holds that key; pages under it were released
        # by the pool (their table slots name the null page) and are
        # not fetched
        first_key = jnp.maximum(kv_start + blk_off - window + 1, 0)
        first_page = first_key // page_size
        first_slab = first_page // ppf

    def each_live_page(i, slot, op):
        """``op`` on the K and V copy of every live page of slab ``i``:
        one strided DMA a page brings all kv heads of it; pages of the
        last slab past the block's last live page are not fetched, nor
        those of the first under a window's first page."""
        for j in range(ppf):
            p = i * ppf + j
            fetch = p < n_pages
            if window is not None:
                fetch &= p >= first_page

            @pl.when(fetch)
            def _copy():
                page = tbl_ref[row, p]
                op(pltpu.make_async_copy(
                    k_hbm.at[:, page], k_buf.at[slot, j], sem.at[0, slot]))
                op(pltpu.make_async_copy(
                    v_hbm.at[:, page], v_buf.at[slot, j], sem.at[1, slot]))

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(n_slabs > first_slab)
    def _first():
        each_live_page(first_slab, first_slab % 2, lambda c: c.start())

    def _slab(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_slabs)
        def _next():              # flies while slab i is multiplied
            each_live_page(i + 1, 1 - slot, lambda c: c.start())

        each_live_page(i, slot, lambda c: c.wait())

        base = i * slab
        # per-token causal mask, the same for every head: token t of the
        # chunk (absolute position kv_start + blk_off + t) sees kv
        # positions <= its own; tokens past q_len (slot padding) are
        # masked out entirely
        tok = blk_off + jax.lax.broadcasted_iota(
            jnp.int32, (qb, grp, slab), 0)
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (qb, grp, slab), 2)
        ok = (tok < q_len) & (pos <= kv_start + tok) & (pos < kv_len)
        if window is not None:
            ok &= pos > kv_start + tok - window
        ok = ok.reshape(qb * grp, slab)
        # rows of V past the horizon are pool positions no query of this
        # block may see, or slab pages that were not fetched: their
        # weight is exactly 0, and 0 x what lies there must stay 0
        vpos = base + jax.lax.broadcasted_iota(jnp.int32, (slab, d), 0)
        seen = vpos < horizon
        if window is not None:
            seen &= vpos >= first_key

        def slab_of(buf, s_ref, h):
            """Head ``h`` of the slab as f32 ``[slab, d]``; an int8
            page is dequantized with its own per-(head, page) scale, a
            scalar off SMEM indexed by the pool page the DMA read (a
            page that was not fetched takes the last live page's: its
            columns are masked)."""
            pages = [buf[slot, j, h].astype(jnp.float32)
                     for j in range(ppf)]
            if quantized:
                pages = [x * s_ref[h, tbl_ref[row, jnp.minimum(
                    i * ppf + j, n_pages - 1)]]
                    for j, x in enumerate(pages)]
            return jnp.concatenate(pages, axis=0)

        def _head(h, carry):
            q = q_ref[:, h].reshape(qb * grp, d).astype(jnp.float32)
            k = slab_of(k_buf, ks_ref, h)
            v = jnp.where(seen, slab_of(v_buf, vs_ref, h), 0.0)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [qb*grp, slab]
            s = jnp.where(ok, s, _NEG_INF)
            m_prev = m_ref[h]                            # [qb*grp, 1]
            l_prev = l_ref[h]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            e = jnp.exp(s - m_new)                       # [qb*grp, slab]
            l_ref[h] = l_prev * alpha + jnp.sum(e, axis=1, keepdims=True)
            m_ref[h] = m_new
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                e, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # [qb*grp, d]
            return carry

        # unrolled: the heads' matmul -> softmax -> matmul chains are
        # independent, and side by side they hide one another's latency
        jax.lax.fori_loop(0, hkv, _head, None, unroll=True)
        return carry

    jax.lax.fori_loop(first_slab, n_slabs, _slab, None)

    def _write(h, carry):
        o_ref[:, h] = (acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)) \
            .reshape(qb, grp, d).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, hkv, _write, None)


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
                           block_row=None, window=None):
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
    window:       None (every key up to the query's own), or the number
        of keys a query sees, itself included: the token at position
        ``p`` attends ``p - window < j <= p``. Static: a window layer
        is another program. ``block_tables`` slots wholly under a row's
        window may name any page (the pool releases them); they are
        neither fetched nor seen.
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
    if window is not None and int(window) < 1:
        raise ValueError(f"window must be >= 1 or None, got {window}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    return _ragged_call(q, k_pages, v_pages, block_tables, q_starts, q_lens,
                        kv_lens, k_scales, v_scales, block_row,
                        q_block=q_block, scale=float(scale),
                        interpret=interpret,
                        window=None if window is None else int(window))


# jitted on its own so that a step which calls the kernel once a layer
# traces and lowers the body ONCE and calls it L times: the body's
# unrolled copies and heads cost seconds of lowering a layer otherwise
# (14 s of a 12-layer step's first call on the chip's host), and that
# is paid before the compile cache can be asked
@functools.partial(jax.jit, static_argnames=("q_block", "scale", "interpret",
                                             "window"))
def _ragged_call(q, k_pages, v_pages, block_tables, q_starts, q_lens,
                 kv_lens, k_scales, v_scales, block_row, *, q_block, scale,
                 interpret, window=None):
    t, hq, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    group = hq // hkv
    pages_per_seq = block_tables.shape[1]
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

    # the body's DMAs slice the pools in HBM, and the chip's compiler
    # slices an HBM ref only by whole 128-lane rows: for it a narrower
    # head is zero-padded to the lane width (exact: the padding adds 0
    # to every score and lands in output columns cut off below). XLA
    # already relaid such a pool out lane-padded, by a pool-sized copy,
    # for any Mosaic call; the interpreter slices anything
    dl = d if interpret else -(-d // 128) * 128
    if dl != d:
        q, k_pages, v_pages = (
            jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, dl - d),))
            for x in (q, k_pages, v_pages))
    qg = q.reshape(t, hkv, group, dl)
    ppf = ragged_slab_pages(page_size, pages_per_seq)

    def _q_map(g, *prefetch):
        return (g, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # block_row, q_starts, q_lens, kv_lens, block_tables
        # (+ k/v scales for int8 pools)
        num_scalar_prefetch=7 if quantized else 5,
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((q_block, hkv, group, dl), _q_map),
            # the pools stay in HBM: the body fetches the pages its
            # block's horizon covers, and no others
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((q_block, hkv, group, dl), _q_map),
        scratch_shapes=[
            # K and V slabs, double-buffered; a page is a leading index
            # so an int8 page (half an int8 tile) lands whole
            pltpu.VMEM((2, ppf, hkv, page_size, dl), k_pages.dtype),
            pltpu.VMEM((2, ppf, hkv, page_size, dl), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),                     # K/V, slot
            pltpu.VMEM((hkv, q_block * group, 1), jnp.float32),  # m
            pltpu.VMEM((hkv, q_block * group, 1), jnp.float32),  # l
            pltpu.VMEM((hkv, q_block * group, dl), jnp.float32),  # acc
        ],
    )
    prefetch = [block_row, q_starts,
                q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32),
                block_tables.astype(jnp.int32)]
    if quantized:
        prefetch += [k_scales.astype(jnp.float32),
                     v_scales.astype(jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, page_size=page_size,
                          q_block=q_block, scale=scale,
                          quantized=quantized, window=window),
        out_shape=jax.ShapeDtypeStruct((t, hkv, group, dl), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret, name="ragged_paged_attention",
    )(*prefetch, qg, k_pages, v_pages)
    return out.reshape(t, hq, dl)[..., :d]


_APPEND_BUFS = 8      # pages in flight: a read, a patch and a write overlap


def _append_kernel(slot_ref, slots_ref, x_ref, p_in, p_out, buf, rsem, wsem,
                   run_ref, *, page_size, tokens, window, null_page):
    ps, T, NB, W = page_size, tokens, _APPEND_BUFS, window

    def page_of(t):
        return slot_ref[t] // ps

    # a run: the adjacent tokens of one pool page (a row's tokens are
    # packed side by side in position order, so a page's are adjacent);
    # dead tokens name the null page and start none
    def _scan(t, n):
        page = page_of(t)
        prev = jnp.where(t > 0, page_of(jnp.maximum(t - 1, 0)), -1)
        start = (page != prev) & (page != null_page)

        @pl.when(start)
        def _():
            run_ref[n] = t
        return n + start.astype(jnp.int32)

    n_runs = jax.lax.fori_loop(0, T, _scan, jnp.int32(0))

    def read(r):
        return pltpu.make_async_copy(
            p_in.at[:, page_of(run_ref[r])], buf.at[r % NB], rsem.at[r % NB])

    def write(r):
        return pltpu.make_async_copy(
            buf.at[r % NB], p_out.at[:, page_of(run_ref[r])], wsem.at[r % NB])

    def _start_read(r, carry):
        read(r).start()
        return carry

    jax.lax.fori_loop(0, jnp.minimum(NB, n_runs), _start_read, None)
    row = jax.lax.broadcasted_iota(jnp.int32, (W, ps), 1)

    def _run(r, carry):
        @pl.when((r >= 1) & (r - 1 + NB < n_runs))
        def _():                  # the buffer run r - 1 wrote from is free
            write(r - 1).wait()
            read(r - 1 + NB).start()

        t0 = run_ref[r]
        # the run lies inside the W tokens from the 16-aligned start
        # under t0; pick[w, j]: token tw + w lands on this page's row j
        tw = pl.multiple_of((t0 // 16) * 16, 16)
        pick = (slots_ref[pl.ds(tw, W), :] == page_of(t0) * ps + row) \
            .astype(jnp.float32).astype(x_ref.dtype)
        # rows picked by a 0/1 product: one term a sum, so exact (an
        # f32 pool needs the f32 passes for that)
        exact = jax.lax.Precision.HIGHEST \
            if x_ref.dtype == jnp.float32 else None
        rows = jax.lax.dot_general(
            jnp.broadcast_to(pick, (x_ref.shape[0], W, ps)),
            x_ref[:, pl.ds(tw, W), :], (((1,), (1,)), ((0,), (0,))),
            precision=exact, preferred_element_type=jnp.float32)
        new_row = jax.lax.dot_general(
            pick, jnp.ones((W, x_ref.shape[2]), x_ref.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > 0
        read(r).wait()
        buf[r % NB] = jnp.where(new_row, rows.astype(buf.dtype),
                                buf[r % NB])
        write(r).start()
        return carry

    jax.lax.fori_loop(0, n_runs, _run, None)

    def _wait_write(r, carry):
        write(r).wait()
        return carry

    jax.lax.fori_loop(jnp.maximum(n_runs - NB, 0), n_runs, _wait_write, None)


# jitted on its own, as ``_ragged_call`` is: a step appends twice a
# layer and should trace and lower the body once
@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_append(pages, slot, x, *, interpret=False):
    """A step's new K or V rows written into the paged pool where it
    lies: token ``t``'s ``x[:, t]`` lands on pool row ``slot[t]``
    (``page * page_size + position % page_size``) of every kv head.

    pages: [num_kv_heads, num_pages, page_size, head_dim], donated by the
        caller's step: the call aliases it to its result and touches
        only the pages that gain a token (fetch, patch in VMEM, write
        back; ``_APPEND_BUFS`` in flight). Time follows pages touched,
        not the pool: a scatter along the slot axis made XLA turn the
        whole pool slot-major and back, twice its bytes a call.
    slot:  [T] int32. Tokens of one page are adjacent (a row's tokens
        are packed in position order) and at most ``page_size``; a dead
        token names a row of the null page
        (``serving/kv_cache.py::NULL_PAGE``) and is dropped: the null
        page is never written.
    x:     [num_kv_heads, T, head_dim].
    """
    from ..serving.kv_cache import NULL_PAGE
    hkv, _, ps, d = pages.shape
    t = slot.shape[0]
    slot = slot.astype(jnp.int32)
    # a patch reads the new tokens from the 16-aligned start under its
    # run's first (a packed dtype's sublane tile), so a page's worth
    # beyond 16; a head under the 128 lanes is zero-padded to them for
    # the chip's compiler and cut back, as ``_ragged_call`` pads it (two
    # pool-sized passes: such a pool is relaid out for any Mosaic call)
    window = 16 + -(-ps // 16) * 16
    tp = -(-t // 16) * 16 + window - 16
    dl = d if interpret else -(-d // 128) * 128
    x = jnp.pad(x.astype(pages.dtype), ((0, 0), (0, tp - t), (0, dl - d)))
    if dl != d:
        pages = jnp.pad(pages, ((0, 0),) * 3 + ((0, dl - d),))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=[pl.BlockSpec((tp, 1), lambda g, s: (0, 0)),
                  pl.BlockSpec((hkv, tp, dl), lambda g, s: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((_APPEND_BUFS, hkv, ps, dl), pages.dtype),
                        pltpu.SemaphoreType.DMA((_APPEND_BUFS,)),
                        pltpu.SemaphoreType.DMA((_APPEND_BUFS,)),
                        pltpu.SMEM((t,), jnp.int32)])
    return pl.pallas_call(
        functools.partial(_append_kernel, page_size=ps, tokens=t,
                          window=window, null_page=NULL_PAGE),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        grid_spec=grid_spec, input_output_aliases={3: 0},
        interpret=interpret, name="kv_append",
    )(slot, jnp.pad(slot, (0, tp - t), constant_values=-1)[:, None], x,
      pages)[..., :d]


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
                                     k_scales=None, v_scales=None,
                                     window=None):
    """jnp oracle for the ragged kernel: per sequence, gather its pages
    densely and run a causally-masked softmax over its chunk's queries
    (each seeing its last ``window`` keys only, where one is given);
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
        if window is not None:
            ok &= pos[None, None, None, :] > limit - window
        s = jnp.where(jnp.asarray(ok), s, _NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqs,hsd->qhgd", w, v).reshape(ql, hq, d)
        out[qs:qs + ql] = np.asarray(o)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# latent (MLA) pages: one compressed row a token, every head against it
# ---------------------------------------------------------------------------

def _latent_kernel(row_ref, qs_ref, ql_ref, kl_ref, tbl_ref, q_ref, c_hbm,
                   o_ref, c_buf, sem, m_ref, l_ref, acc_ref, *, page_size,
                   q_block, heads, v_width):
    g = pl.program_id(0)          # q block
    row = row_ref[g]
    q_len = ql_ref[row]
    kv_len = kl_ref[row]
    kv_start = kv_len - q_len
    blk_off = g * q_block - qs_ref[row]
    H = heads
    slab = c_buf.shape[1]         # KV tokens a fetch
    ppf = slab // page_size

    live_block = (blk_off >= 0) & (blk_off < q_len)
    horizon = jnp.where(
        live_block, jnp.minimum(kv_len, kv_start + blk_off + q_block), 0)
    n_pages = pl.cdiv(horizon, page_size)
    n_slabs = pl.cdiv(horizon, slab)

    def each_live_page(i, slot, op):
        """``op`` on the copy of every live page of slab ``i``: one DMA
        a page brings its ``page_size`` whole rows; pages of the last
        slab past the block's last live page are not fetched."""
        for j in range(ppf):
            p = i * ppf + j

            @pl.when(p < n_pages)
            def _copy():
                op(pltpu.make_async_copy(
                    c_hbm.at[tbl_ref[row, p]],
                    c_buf.at[slot, pl.ds(j * page_size, page_size)],
                    sem.at[slot]))

    def walk(nq):
        """The block's first ``nq`` tokens (static) against the walk:
        ``nq * heads`` query rows ride each product. A decode row is
        ``nq = 1``: its block's other rows are slot padding, and their
        share of the two products would be seven eighths of the work."""
        rows = nq * H
        m_ref[:rows] = jnp.full((rows, 1), _NEG_INF, jnp.float32)
        l_ref[:rows] = jnp.zeros((rows, 1), jnp.float32)
        acc_ref[:rows] = jnp.zeros((rows, v_width), jnp.float32)

        @pl.when(n_slabs > 0)
        def _first():
            each_live_page(0, 0, lambda c: c.start())

        # the last key each query row may see: its own position (causal),
        # and for a slot's padding tokens nothing past the row's context
        tok = blk_off + jax.lax.broadcasted_iota(
            jnp.int32, (nq, H, 1), 0).reshape(rows, 1)
        last = jnp.minimum(kv_start + tok, kv_len - 1)
        # slabs wholly under the block's FIRST query's position are seen
        # whole by every row of it (and were fetched whole): no mask, and
        # most of a long context's walk
        n_clear = jnp.minimum((kv_start + blk_off + 1) // slab, n_slabs)

        def _slab(i, carry, masked):
            slot = i % 2

            @pl.when(i + 1 < n_slabs)
            def _next():          # flies while slab i is multiplied
                each_live_page(i + 1, 1 - slot, lambda c: c.start())

            each_live_page(i, slot, lambda c: c.wait())
            base = i * slab
            c = c_buf[slot]                               # [slab, W]
            # the whole row is the key; its first v_width values are
            # also the value
            v = c[:, :v_width]
            s = jax.lax.dot_general(
                q_ref[:rows], c, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # [rows, slab]
            if masked:
                # rows past the horizon are pool positions no query of
                # this block may see, or pages that were not fetched:
                # their weight is exactly 0, and 0 x what lies there
                # must stay 0
                vpos = base + jax.lax.broadcasted_iota(
                    jnp.int32, (slab, v_width), 0)
                v = jnp.where(vpos < horizon, v, 0)
                pos = base + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, slab), 1)
                s = jnp.where(pos <= last, s, _NEG_INF)
            m_prev = m_ref[:rows]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            e = jnp.exp(s - m_new)
            l_ref[:rows] = l_ref[:rows] * alpha \
                + jnp.sum(e, axis=1, keepdims=True)
            m_ref[:rows] = m_new
            acc_ref[:rows] = acc_ref[:rows] * alpha + jax.lax.dot_general(
                e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)       # [rows, v_width]
            return carry

        jax.lax.fori_loop(
            0, n_clear, functools.partial(_slab, masked=False), None)
        jax.lax.fori_loop(
            n_clear, n_slabs, functools.partial(_slab, masked=True), None)
        o_ref[:rows] = (acc_ref[:rows] / jnp.maximum(l_ref[:rows], 1e-30)) \
            .astype(o_ref.dtype)

    if q_block == 1:
        walk(1)
        return
    one = live_block & (q_len - blk_off == 1)

    @pl.when(one)
    def _decode():
        o_ref[...] = jnp.zeros_like(o_ref)
        walk(1)

    @pl.when(jnp.logical_not(one))
    def _chunk():
        walk(q_block)


def ragged_latent_attention(q, c_pages, block_tables, q_starts, q_lens,
                            kv_lens, *, v_width, scale, q_block=8,
                            interpret=False):
    """The ragged step's attention over a LATENT paged cache (multi-head
    latent attention in its absorbed form): a token holds ONE row in the
    pool, every head's key, and the row's first ``v_width`` values are
    every head's value. ``score[t, h, j] = scale * q[t, h] . c[j]`` for
    ``j <= t``'s position; ``out[t, h] = softmax(score) c[:, :v_width]``.

    q:        [total_q_tokens, heads, W] — each head's query in the
        row's coordinates (``[q_nope W_uk ; q_rope]``, zero where the
        row is padded), packed row-wise as
        :func:`ragged_paged_attention` packs them.
    c_pages:  [num_pages, page_size, W] — no kv-head axis, no K and V.
    block_tables, q_starts, q_lens, kv_lens: as the ragged kernel's.
    Returns [total_q_tokens, heads, v_width]; padding rows hold finite
    garbage and must be ignored by the caller.

    The walk is the ragged kernel's (q blocks over slabs of
    ``ragged_slab_pages`` pages, double-buffered, ending at the block's
    causal horizon); what differs is the product: all ``heads`` of a q
    block's tokens ride ONE ``[q_block * heads, W] x [W, slab]`` matmul a
    slab, and a decode row's single live token its ``[heads, W]`` part
    of it. Products run in the pool's dtype with float32 accumulation.
    """
    t, h, w = q.shape
    _, page_size, wc = c_pages.shape
    if wc != w:
        raise ValueError(f"row width mismatch: q {w} vs pages {wc}")
    if not 0 < v_width <= w:
        raise ValueError(f"v_width {v_width} outside the row's {w}")
    if t % q_block != 0:
        raise ValueError(f"total_q_tokens {t} not a multiple of q_block "
                         f"{q_block}")
    return _latent_call(q, c_pages, block_tables, q_starts, q_lens, kv_lens,
                        v_width=int(v_width), scale=float(scale),
                        q_block=q_block, interpret=interpret)


# jitted on its own, as ``_ragged_call`` is
@functools.partial(jax.jit, static_argnames=("v_width", "scale", "q_block",
                                             "interpret"))
def _latent_call(q, c_pages, block_tables, q_starts, q_lens, kv_lens, *,
                 v_width, scale, q_block, interpret):
    t, h, w = q.shape
    _, page_size, _ = c_pages.shape
    pages_per_seq = block_tables.shape[1]
    num_blocks = t // q_block
    rows = q_block * h
    q_starts = q_starts.astype(jnp.int32)
    block_row = ragged_block_row(q_starts, num_blocks, q_block)
    # the ragged kernel's slab: 512 and 1,024 tokens read 10-12 % faster
    # on the chip and 2,048 slower (PERF.md section 6, PR 33)
    slab = ragged_slab_pages(page_size, pages_per_seq) * page_size

    def _q_map(g, *prefetch):
        return (g, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(num_blocks,),
        in_specs=[pl.BlockSpec((rows, w), _q_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((rows, v_width), _q_map),
        scratch_shapes=[
            pltpu.VMEM((2, slab, w), c_pages.dtype),   # double-buffered
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((rows, 1), jnp.float32),        # m
            pltpu.VMEM((rows, 1), jnp.float32),        # l
            pltpu.VMEM((rows, v_width), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_kernel, page_size=page_size,
                          q_block=q_block, heads=h, v_width=v_width),
        out_shape=jax.ShapeDtypeStruct((t * h, v_width), q.dtype),
        grid_spec=grid_spec, interpret=interpret,
        name="ragged_latent_attention",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
    )(block_row, q_starts, q_lens.astype(jnp.int32),
      kv_lens.astype(jnp.int32), block_tables.astype(jnp.int32),
      # the softmax scale rides the queries: one multiply a query value
      # here, not one a score in the body
      (q * scale).astype(c_pages.dtype).reshape(t * h, w), c_pages)
    return out.reshape(t, h, v_width)


def ragged_latent_attention_reference(q, c_pages, block_tables, q_starts,
                                      q_lens, kv_lens, *, v_width, scale):
    """jnp oracle for :func:`ragged_latent_attention`: per sequence,
    gather its rows densely and run a causally-masked softmax over its
    chunk's queries; rows outside any live slot stay zero."""
    t, h, w = q.shape
    out = np.zeros((t, h, v_width), np.float32)
    q_starts, q_lens, kv_lens = (np.asarray(x) for x in
                                 (q_starts, q_lens, kv_lens))
    for i in range(len(q_lens)):
        ql, kl = int(q_lens[i]), int(kv_lens[i])
        if ql == 0:
            continue
        qs = int(q_starts[i])
        c = c_pages[block_tables[i]].astype(jnp.float32).reshape(-1, w)
        s = jnp.einsum("qhw,sw->hqs", q[qs:qs + ql].astype(jnp.float32),
                       c) * scale
        pos = np.arange(s.shape[-1])
        limit = (kl - ql + np.arange(ql))[None, :, None]
        ok = (pos[None, None, :] <= limit) & (pos[None, None, :] < kl)
        p = jax.nn.softmax(jnp.where(jnp.asarray(ok), s, _NEG_INF), axis=-1)
        out[qs:qs + ql] = np.asarray(
            jnp.einsum("hqs,sv->qhv", p, c[:, :v_width]))
    return jnp.asarray(out)


__all__ = ["kv_append", "paged_attention", "paged_attention_reference",
           "ragged_block_row", "ragged_kv_tokens_read",
           "ragged_latent_attention", "ragged_latent_attention_reference",
           "ragged_paged_attention", "ragged_paged_attention_reference",
           "ragged_slab_pages"]
