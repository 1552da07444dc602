"""Paged KV-cache decode attention: kernel parity (interpret mode) + paged
Generator exactness vs the dense-cache engine (reference capability:
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels.paged_attention import (
    paged_attention, paged_attention_reference)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config, Generator


@pytest.mark.parametrize("lens", [[37, 64, 5], [1, 1, 1], [64, 64, 64]])
def test_kernel_parity_variable_lengths(lens):
    rng = np.random.default_rng(0)
    b, hq, hkv, d, ps, npages, pps = 3, 8, 2, 64, 16, 24, 4
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(npages)[:b * pps].reshape(b, pps),
                      jnp.int32)
    sl = jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, kp, vp, tbl, sl, interpret=True)
    ref = paged_attention_reference(q, kp, vp, tbl, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("lens", [
    [15, 16, 17, 31, 33],     # straddling every boundary of ps=16 pages
    [16, 32, 48, 64, 1],      # exact page multiples (last-live-page edge)
    [63, 2, 18, 47, 64],      # interior + full-pool mix
])
def test_kernel_parity_ragged_lengths_cross_page_boundaries(lens):
    """Off-TPU (interpreter) parity for ragged lengths landing just
    before, exactly on, and just after page boundaries — the clamp in the
    kernel's index map and the in-page masking are both load-bearing."""
    rng = np.random.default_rng(7)
    b, hq, hkv, d, ps = 5, 4, 2, 32, 16
    pps = 4                               # covers up to 64 tokens
    npages = b * pps + 3                  # a few never-referenced pages
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(npages)[:b * pps].reshape(b, pps),
                      jnp.int32)
    sl = jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, kp, vp, tbl, sl, interpret=True)
    ref = paged_attention_reference(q, kp, vp, tbl, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernel_parity_jit_wrapped():
    """The serving decode step calls the kernel from inside jit; the
    interpreter path must hold parity there too."""
    rng = np.random.default_rng(9)
    b, hq, hkv, d, ps, pps = 2, 4, 2, 32, 8, 3
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, b * pps, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, b * pps, ps, d)), jnp.float32)
    tbl = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
    sl = jnp.asarray([17, 9], jnp.int32)
    out = jax.jit(lambda *a: paged_attention(*a, interpret=True))(
        q, kp, vp, tbl, sl)
    ref = paged_attention_reference(q, kp, vp, tbl, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernel_parity_mha_no_gqa():
    rng = np.random.default_rng(1)
    b, h, d, ps, pps = 2, 4, 32, 8, 3
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((h, b * pps, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((h, b * pps, ps, d)), jnp.float32)
    tbl = jnp.arange(b * pps, dtype=jnp.int32).reshape(b, pps)
    sl = jnp.asarray([17, 9], jnp.int32)
    out = paged_attention(q, kp, vp, tbl, sl, interpret=True)
    ref = paged_attention_reference(q, kp, vp, tbl, sl)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_paged_generator_matches_dense():
    """Greedy decode through the paged Pallas path must emit exactly the
    dense-cache engine's tokens."""
    paddle.seed(11)
    cfg = llama_tiny_config(num_key_value_heads=2)
    model = LlamaForCausalLM(cfg)
    ids = np.random.RandomState(3).randint(0, cfg.vocab_size, (2, 6))
    dense = Generator(model, max_len=32)
    out_dense = dense.generate(paddle.to_tensor(ids, dtype="int64"),
                               max_new_tokens=6, temperature=0.0).numpy()
    paged = Generator(model, max_len=32, paged=True, page_size=8)
    out_paged = paged.generate(paddle.to_tensor(ids, dtype="int64"),
                               max_new_tokens=6, temperature=0.0).numpy()
    np.testing.assert_array_equal(out_dense, out_paged)


# ---------------------------------------------------------------------------
# ragged kernel: one program for mixed decode rows + prefill chunks
# ---------------------------------------------------------------------------

from paddle_tpu.kernels.paged_attention import (  # noqa: E402
    ragged_paged_attention, ragged_paged_attention_reference)


def _pack_rows(q_lens, q_block, budget):
    """Slot starts aligned to q_block; pad rows start past the budget."""
    starts, cursor = [], 0
    for ql in q_lens:
        if ql == 0:
            starts.append(budget)
            continue
        starts.append(cursor)
        cursor += -(-ql // q_block) * q_block
    assert cursor <= budget
    return np.asarray(starts, np.int32)


def _quantize_pools(kp, vp):
    """int8 pools with per-(head, page) absmax scales, as the engine's
    quantized KV cache holds them."""
    out = []
    for pool in (np.asarray(kp), np.asarray(vp)):
        s = np.maximum(np.abs(pool).max(axis=(2, 3)), 1e-8) / 127.0
        out += [jnp.asarray(np.clip(np.round(pool / s[:, :, None, None]),
                                    -127, 127).astype(np.int8)),
                jnp.asarray(s)]
    kq, ks, vq, vs = out
    return kq, vq, dict(k_scales=ks, v_scales=vs)


def _ragged_case(q_lens, kv_lens, *, qb=4, budget=32, hq=4, hkv=2, d=32,
                 ps=8, pps=6, seed=0, quant=False):
    rng = np.random.default_rng(seed)
    n = len(q_lens)
    npages = n * pps + 3
    q = jnp.asarray(rng.standard_normal((budget, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(npages)[:n * pps].reshape(n, pps),
                      jnp.int32)
    q_starts = _pack_rows(q_lens, qb, budget)
    args = dict(q_starts=jnp.asarray(q_starts),
                q_lens=jnp.asarray(q_lens, jnp.int32),
                kv_lens=jnp.asarray(kv_lens, jnp.int32))
    scales = {}
    if quant:
        kp, vp, scales = _quantize_pools(kp, vp)
    out = ragged_paged_attention(q, kp, vp, tbl, q_block=qb,
                                 interpret=True, **args, **scales)
    ref = ragged_paged_attention_reference(q, kp, vp, tbl, q_starts,
                                           np.asarray(q_lens),
                                           np.asarray(kv_lens), **scales)
    return np.asarray(out), np.asarray(ref), q_starts


def _assert_live_rows_close(out, ref, q_starts, q_lens, tol=2e-4):
    for s, ql in zip(q_starts, q_lens):
        if ql:
            np.testing.assert_allclose(out[s:s + ql], ref[s:s + ql],
                                       rtol=tol, atol=tol)


def test_ragged_mixed_decode_and_prefill_chunks():
    """Decode rows (q_len=1), a fresh-prompt chunk (kv_len == q_len, the
    fully causal case), a mid-prompt chunk (kv_len > q_len), and a pad
    row (q_len=0) in ONE launch match the dense causal oracle."""
    q_lens = [1, 5, 1, 7, 0]
    kv_lens = [13, 5, 33, 20, 0]
    out, ref, starts = _ragged_case(q_lens, kv_lens)
    _assert_live_rows_close(out, ref, starts, q_lens)


@pytest.mark.parametrize("q_lens,kv_lens", [
    ([1, 1, 1, 1], [15, 16, 17, 31]),      # all-decode, page boundaries
    ([8, 8], [8, 48]),                     # chunk exactly one q_block
    ([3, 6, 2], [11, 41, 2]),              # ragged chunks, ragged kv
])
def test_ragged_parity_across_page_boundaries(q_lens, kv_lens):
    out, ref, starts = _ragged_case(q_lens, kv_lens, seed=3)
    _assert_live_rows_close(out, ref, starts, q_lens)


def test_ragged_int8_pages_within_tolerance():
    """int8 pages + per-(head, page) scales through the ragged kernel
    match the quantized oracle exactly (same math) — the int8-KV path
    rides the ragged kernel unchanged."""
    q_lens = [1, 6, 2]
    kv_lens = [19, 22, 7]
    out, ref, starts = _ragged_case(q_lens, kv_lens, seed=5, quant=True,
                                    qb=2, budget=16)
    _assert_live_rows_close(out, ref, starts, q_lens, tol=1e-4)


def test_ragged_jit_wrapped_and_chunk_split_invariance():
    """Inside jit (the serving step calls it there), and: splitting one
    prompt's queries across two chunk launches reproduces the
    whole-chunk outputs — the numerical basis for chunked prefill's
    token identity."""
    rng = np.random.default_rng(9)
    hq, hkv, d, ps, pps, qb = 4, 2, 16, 8, 4, 4
    npages = pps + 2
    L = 12                                   # whole prompt
    budget = 16
    kp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((hkv, npages, ps, d)), jnp.float32)
    tbl = jnp.asarray(np.arange(1, pps + 1, dtype=np.int32)[None])
    qtok = rng.standard_normal((L, hq, d)).astype(np.float32)

    def run(q_rows, q_len, kv_len):
        q = np.zeros((budget, hq, d), np.float32)
        q[:q_len] = q_rows
        f = jax.jit(lambda *a: ragged_paged_attention(
            *a, q_block=qb, interpret=True))
        return np.asarray(f(
            jnp.asarray(q), kp, vp, tbl,
            jnp.asarray([0], jnp.int32), jnp.asarray([q_len], jnp.int32),
            jnp.asarray([kv_len], jnp.int32)))[:q_len]

    whole = run(qtok, L, L)                  # one 12-token chunk
    first = run(qtok[:8], 8, 8)              # chunked: 8 then 4
    second = run(qtok[8:], 4, L)
    np.testing.assert_allclose(np.concatenate([first, second]), whole,
                               rtol=1e-5, atol=1e-6)


def test_ragged_rejects_misaligned_budget():
    with pytest.raises(ValueError, match="q_block"):
        ragged_paged_attention(
            jnp.zeros((10, 4, 8)), jnp.zeros((2, 4, 4, 8)),
            jnp.zeros((2, 4, 4, 8)), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
            jnp.ones((1,), jnp.int32), q_block=4, interpret=True)


# ---------------------------------------------------------------------------
# the walk over live KV: a loop whose trip count follows each q block's
# causal horizon, a slab of pages a fetch
# ---------------------------------------------------------------------------

from paddle_tpu.kernels.paged_attention import (  # noqa: E402
    ragged_kv_tokens_read, ragged_slab_pages)

_PS = 16
_SLAB = ragged_slab_pages(_PS, 1 << 20) * _PS     # KV tokens a fetch
_PPS = 3 * _SLAB // _PS                           # max_len: three slabs
# (q_lens, kv_lens) per edge of the trip count; pad rows (q_len 0) last
_WALK_EDGES = {
    "every_row_dead": ([0, 0, 0], [0, 0, 0]),
    "decode_at_kv_len_1": ([1], [1]),
    "one_under_a_slab": ([1], [_SLAB - 1]),
    "exactly_a_slab": ([1], [_SLAB]),
    "one_over_a_slab": ([1], [_SLAB + 1]),
    "row_at_max_len": ([1], [_PS * _PPS]),
    # kv_start 28 under the slab boundary: the 8-token blocks' horizons
    # run from 20 under it to 36 over it
    "chunk_across_slabs": ([64], [_SLAB + 36]),
    "mixed_with_pad_rows": ([1, 64, 1, 0, 0],
                            [_SLAB + 1, _SLAB + 72, _PS * _PPS, 0, 0]),
}
_WALK_BUDGET = {1: 72, 8: 96, 128: 384}
_WALK_ROWS = 5


def _walk_operands(q_lens, kv_lens, qb, seed=0):
    """Fixed shapes over every edge (so one compile a (q_block, pool
    dtype)): rows padded to ``_WALK_ROWS`` with ``q_start = T``."""
    pad = _WALK_ROWS - len(q_lens)
    q_lens, kv_lens = list(q_lens) + [0] * pad, list(kv_lens) + [0] * pad
    rng = np.random.default_rng(seed)
    hq, hkv, d, T = 4, 2, 32, _WALK_BUDGET[qb]
    npages = _WALK_ROWS * _PPS + 3
    q = rng.standard_normal((T, hq, d)).astype(np.float32)
    kp = rng.standard_normal((hkv, npages, _PS, d)).astype(np.float32)
    vp = rng.standard_normal((hkv, npages, _PS, d)).astype(np.float32)
    tbl = (rng.permutation(npages - 1)[:_WALK_ROWS * _PPS] + 1) \
        .reshape(_WALK_ROWS, _PPS).astype(np.int32)
    return (q, kp, vp, tbl, _pack_rows(q_lens, qb, T),
            np.asarray(q_lens, np.int32), np.asarray(kv_lens, np.int32))


_walk_kernel = jax.jit(ragged_paged_attention,
                       static_argnames=("q_block", "interpret"))


@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("qb", [1, 8, 128])
@pytest.mark.parametrize("edge", list(_WALK_EDGES))
def test_ragged_walk_follows_live_kv(edge, qb, quant):
    q_lens, kv_lens = _WALK_EDGES[edge]
    q, kp, vp, tbl, qs, ql, kl = _walk_operands(q_lens, kv_lens, qb)
    scales = {}
    if quant:
        kp, vp, scales = _quantize_pools(kp, vp)
    out = np.asarray(_walk_kernel(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(kl), q_block=qb,
        interpret=True, **scales))
    ref = np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        qs, ql, kl, **scales))
    assert np.isfinite(out).all()
    _assert_live_rows_close(out, ref, qs, ql)


@pytest.mark.parametrize("qb", [1, 8, 128])
def test_ragged_poisoned_pool_never_reaches_the_output(qb):
    """Every pool position no live query may see holds NaN: pages no
    live row's table reaches within its kv_len, the tail of a row's
    last page, every page of a pad row. A weight of exactly 0 on such a
    position must stay 0 — the output is finite everywhere and the live
    rows equal the oracle's on the clean pool."""
    q_lens, kv_lens = _WALK_EDGES["mixed_with_pad_rows"]
    q, kp, vp, tbl, qs, ql, kl = _walk_operands(q_lens, kv_lens, qb,
                                                seed=11)
    ref = np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        qs, ql, kl))
    seen = np.zeros(kp.shape[1:3], bool)          # [page, slot]
    for i, n in enumerate(kl):
        pos = np.arange(n)
        seen[tbl[i, pos // _PS], pos % _PS] = True
    kp = np.where(seen[None, :, :, None], kp, np.nan)
    vp = np.where(seen[None, :, :, None], vp, np.nan)
    out = np.asarray(_walk_kernel(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(kl), q_block=qb,
        interpret=True))
    assert np.isfinite(out).all()
    _assert_live_rows_close(out, ref, qs, ql)


# the counts' geometry: 8 q heads a kv head (Qwen3-Next's, K-EXAONE's),
# so a row longer than q_block 8 or 1 is walked 64 tokens at a time; at
# q_block 128 the tile is the block and every row is walked a block at
# a time
_COUNT_GROUP, _COUNT_BUDGET = 8, 512


@pytest.mark.parametrize("q_lens,kv_lens,qb,want", [
    ([0, 0], [0, 0], 8, 0),                   # nothing aboard
    ([1], [1], 8, _SLAB),                     # a row rounds up to a slab
    ([1, 1, 1], [_SLAB - 1, _SLAB, _SLAB + 1], 8, 4 * _SLAB),
    # kv_start 28 under the slab boundary: one 64-token tile whose last
    # query's horizon is 36 over it, two slabs (a q block of 8 at a
    # time it was eight walks, three of one slab and five of two)
    ([64], [_SLAB + 36], 8, 2 * _SLAB),
    ([64], [_SLAB + 36], 128, 2 * _SLAB),     # one block sees it once
    # q_block 1: the same tile (sixty-four one-token walks before, 28 of
    # one slab and 36 of two)
    ([64, 0], [_SLAB + 36, 0], 1, 2 * _SLAB),
    # kv_start at the slab boundary, 200 tokens: tiles at 0, 64, 128 and
    # 192 (8 wide), horizons 320, 384, 448, 456: two slabs each
    ([200], [_SLAB + 200], 8, 4 * 2 * _SLAB),
    # a decode row beside two chunks: 1 slab; a 70-token chunk from 0,
    # tiles of 64 and 6 with horizons 64 and 70, one slab each; a 16-token
    # chunk behind 500, one tile with horizon 516, three slabs
    ([1, 70, 16], [3, 70, 516], 8, _SLAB + 2 * _SLAB + 3 * _SLAB),
])
def test_ragged_kv_tokens_read_counts_the_walk(q_lens, kv_lens, qb, want):
    """The count on ``serve.step``: each walk's causal horizon rounded up
    to the slab, summed — what one kv head's loops cover. A row of at
    most ``q_block`` tokens is walked a q block at a time, a longer one a
    q tile at a time."""
    tile = ragged_q_tile(qb, _COUNT_GROUP, _COUNT_BUDGET)
    assert tile == (128 if qb == 128 else 64)
    assert ragged_kv_tokens_read(q_lens, kv_lens, q_block=qb, q_tile=tile,
                                 page_size=_PS, pages_per_seq=_PPS) == want


@pytest.mark.parametrize("q_lens,qb,want", [
    ([0, 0], 8, 0),                   # nothing aboard: no walk
    ([1, 1, 1, 0], 8, 3),             # a decode row: one block
    ([8, 9], 8, 2),                   # one block; a 9-token chunk: a tile
    ([64, 65, 200], 8, 1 + 2 + 4),    # tiles of 64: 1, 2 and 4 walks
    ([64, 1], 1, 1 + 1),              # q_block 1: the chunk is one tile
    ([200, 1], 128, 2 + 1),           # tile = q_block 128: blocks only
    ([4, 200], 4, 1 + 4),             # spec rounds of 4 at q_block 4
])
def test_ragged_kv_walks_counts_blocks_and_tiles(q_lens, qb, want):
    """``attn_kv_walks`` on ``serve.step``: the walks one layer's call
    makes, a live q block of each row of at most ``q_block`` tokens and a
    q tile of each longer one — the walks the count above sums."""
    tile = ragged_q_tile(qb, _COUNT_GROUP, _COUNT_BUDGET)
    assert ragged_kv_walks(q_lens, q_block=qb, q_tile=tile) == want
    # every context here is under a slab: each walk reads one
    assert ragged_kv_tokens_read(
        q_lens, [n + 1 for n in q_lens], q_block=qb, q_tile=tile,
        page_size=_PS, pages_per_seq=_PPS) == want * _SLAB


# ---------------------------------------------------------------------------
# the wide walk: a row longer than a q block walked a q tile at a time
# ---------------------------------------------------------------------------

from paddle_tpu.kernels.paged_attention import (  # noqa: E402
    ragged_kv_walks, ragged_q_tile)

# (q heads, kv heads, head_dim, q_lens, kv_lens): decode rows first, so
# that each chunk's slot starts at a q block that is no multiple of the
# tile; each chunk spans two or three tiles, its last one shorter
_TILE_GEOMETRIES = {
    # Qwen3-Next's full layers: 8 q heads a kv head of 256 (tile 64)
    "g8_d256": (8, 1, 256, [1, 150, 1, 70, 0], [37, 450, 513, 330, 0]),
    # Mistral-7B's: 4 q heads a kv head of 128 (tile 128); the second
    # chunk is a fresh prompt, kv_len == q_len
    "g4_d128": (8, 2, 128, [1, 200, 1, 136, 0], [300, 460, 37, 136, 0]),
}
# "fp" and "poisoned" share one compile
_TILE_CASES = [("g8_d256", "fp"), ("g8_d256", "poisoned"),
               ("g8_d256", "window"), ("g4_d128", "int8")]
_tile_kernel = jax.jit(ragged_paged_attention,
                       static_argnames=("q_block", "interpret", "window"))


@pytest.mark.parametrize("geo,pool", _TILE_CASES)
def test_ragged_q_tiles_match_the_reference(geo, pool):
    """Chunks beside decode rows in one launch, each walked a q tile at a
    time, against the oracle: f32 and int8 pools; a window layer whose
    pages under each row's window are released (they name page 0) and
    every position no query may see is NaN; a poisoned full layer."""
    hq, hkv, d, q_lens, kv_lens = _TILE_GEOMETRIES[geo]
    # pages of 64: four a slab, so the interpreter unrolls few copies
    qb, ps, window = 8, 64, 24 if pool == "window" else None
    budget = sum(-(-n // qb) * qb for n in q_lens) + qb
    tile = ragged_q_tile(qb, hq // hkv, budget)
    q_starts = _pack_rows(q_lens, qb, budget)
    chunks = [n for n in q_lens if n > qb]
    assert all(s % tile for s, n in zip(q_starts, q_lens) if n > qb)
    assert all(n > tile and n % tile for n in chunks)
    assert ragged_kv_walks(q_lens, q_block=qb, q_tile=tile) \
        == len(q_lens) - 1 - len(chunks) + sum(-(-n // tile) for n in chunks)
    rng = np.random.default_rng(13)
    pps = -(-max(kv_lens) // ps)
    npages = len(q_lens) * pps + 1
    q = rng.standard_normal((budget, hq, d)).astype(np.float32)
    kp = rng.standard_normal((hkv, npages, ps, d)).astype(np.float32)
    vp = rng.standard_normal((hkv, npages, ps, d)).astype(np.float32)
    tbl = (rng.permutation(npages - 1)[:len(q_lens) * pps] + 1) \
        .reshape(len(q_lens), pps).astype(np.int32)
    scales = {}
    if pool == "int8":
        kp, vp, scales = _quantize_pools(kp, vp)
    want = np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        q_starts, np.asarray(q_lens), np.asarray(kv_lens), window=window,
        **scales))
    if pool in ("window", "poisoned"):
        seen = np.zeros(kp.shape[1:3], bool)          # [page, slot]
        for i, (n, m) in enumerate(zip(kv_lens, q_lens)):
            first = max(n - m - window + 1, 0) if window else 0
            pos = np.arange(first, n)
            seen[tbl[i, pos // ps], pos % ps] = True
            tbl[i, :first // ps] = 0                  # released
        kp = np.where(seen[None, :, :, None], kp, np.nan)
        vp = np.where(seen[None, :, :, None], vp, np.nan)
    out = np.asarray(_tile_kernel(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        jnp.asarray(q_starts), jnp.asarray(q_lens, jnp.int32),
        jnp.asarray(kv_lens, jnp.int32), q_block=qb, interpret=True,
        window=window, **scales))
    assert np.isfinite(out).all()
    _assert_live_rows_close(out, want, q_starts, q_lens,
                            tol=1e-4 if pool == "int8" else 2e-4)
