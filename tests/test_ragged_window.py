"""The ragged kernel with a window: a query at position p sees keys j
with p - window < j <= p. Against ``ragged_paged_attention_reference``
with the same mask (interpreted; f32 pools, so the tolerance is the
reference's own accumulation order, 2e-4), across page, slab and window
boundaries, and with ``window=None`` bit-equal to the call without the
argument."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.paged_attention import (
    ragged_kv_tokens_read, ragged_paged_attention,
    ragged_paged_attention_reference, ragged_slab_pages)

PS, PPS, W = 8, 128, 24           # page, table width, window (3 pages)
ROWS = 5
SLAB = ragged_slab_pages(PS, PPS) * PS      # 256 KV tokens a fetch
BUDGET = {1: 8, 8: 96}

_kernel = jax.jit(ragged_paged_attention,
                  static_argnames=("q_block", "interpret", "window"))


def _pack(q_lens, qb, budget):
    starts, cursor = [], 0
    for ql in q_lens:
        starts.append(cursor if ql else budget)
        cursor += -(-ql // qb) * qb
    assert cursor <= budget
    return np.asarray(starts, np.int32)


def _operands(q_lens, kv_lens, qb, seed=0):
    pad = ROWS - len(q_lens)
    q_lens, kv_lens = list(q_lens) + [0] * pad, list(kv_lens) + [0] * pad
    rng = np.random.default_rng(seed)
    hq, hkv, d, T = 4, 2, 32, BUDGET[qb]
    npages = ROWS * PPS + 3
    q = rng.standard_normal((T, hq, d)).astype(np.float32)
    kp = rng.standard_normal((hkv, npages, PS, d)).astype(np.float32)
    vp = rng.standard_normal((hkv, npages, PS, d)).astype(np.float32)
    tbl = (rng.permutation(npages - 1)[:ROWS * PPS] + 1) \
        .reshape(ROWS, PPS).astype(np.int32)
    return (q, kp, vp, tbl, _pack(q_lens, qb, T),
            np.asarray(q_lens, np.int32), np.asarray(kv_lens, np.int32))


def _run(ops, qb, window):
    q, kp, vp, tbl, qs, ql, kl = ops
    return np.asarray(_kernel(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        jnp.asarray(qs), jnp.asarray(ql), jnp.asarray(kl), q_block=qb,
        interpret=True, window=window))


def _ref(ops, window):
    q, kp, vp, tbl, qs, ql, kl = ops
    return np.asarray(ragged_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tbl),
        qs, ql, kl, window=window))


def _close(out, ref, qs, ql, tol=2e-4):
    for s, n in zip(qs, ql):
        if n:
            np.testing.assert_allclose(out[s:s + n], ref[s:s + n],
                                       rtol=tol, atol=tol)


# (q_lens, kv_lens) a row; the window is 24 keys = 3 pages of 8
EDGES = {
    # decode rows: context under, at, one over the window; a window that
    # starts mid-page (kv 45: first key seen is 21 = page 2, slot 5)
    "decode_under_at_over": ([1, 1, 1, 1], [W - 5, W, W + 1, 45]),
    # far past the first slab: the walk starts at slab 1 and 2
    "decode_past_slabs": ([1, 1, 1], [SLAB + 3, 2 * SLAB + W, 40 * W]),
    # the window ends exactly on a page and on a slab boundary
    "decode_on_boundaries": ([1, 1, 1], [W + PS, SLAB, SLAB + W - 1]),
    # a prefill chunk longer than the window, from position 0 and from
    # deep in the context; one shorter than the window
    "chunk_longer_than_window": ([64], [64]),
    "chunk_deep": ([64, 7], [SLAB + 200, 300]),
    # decode rows and prefill rows in one launch, pad rows behind
    "mixed_launch": ([1, 40, 1, 16], [500, SLAB + 40, 9, 16]),
}


# a chunk does not fit the q_block-1 budget: decode-only edges run at both
CASES = [(e, qb) for e in EDGES for qb in (1, 8)
         if qb == 8 or sum(EDGES[e][0]) <= BUDGET[1]]


@pytest.mark.parametrize("edge,qb", CASES)
def test_window_kernel_equals_masked_reference(edge, qb):
    q_lens, kv_lens = EDGES[edge]
    ops = _operands(q_lens, kv_lens, qb)
    out = _run(ops, qb, W)
    assert np.isfinite(out).all()
    _close(out, _ref(ops, W), ops[4], ops[5])
    # the mask is not a no-op here: without it the answer differs
    if max(kv_lens) > W:
        full = _ref(ops, None)
        assert any(np.abs(full[s:s + n] - out[s:s + n]).max() > 1e-3
                   for s, n in zip(ops[4], ops[5]) if n)


def test_window_none_is_bit_equal_to_no_argument():
    ops = _operands(*EDGES["mixed_launch"], 8, seed=3)
    q, kp, vp, tbl, qs, ql, kl = (jnp.asarray(x) for x in ops)
    plain = ragged_paged_attention(q, kp, vp, tbl, qs, ql, kl, q_block=8,
                                   interpret=True)
    none = ragged_paged_attention(q, kp, vp, tbl, qs, ql, kl, q_block=8,
                                  interpret=True, window=None)
    assert np.array_equal(np.asarray(plain), np.asarray(none))
    # and a window no context reaches masks nothing
    wide = _run(ops, 8, 10 ** 6)
    _close(wide, np.asarray(plain), ops[4], ops[5], tol=1e-6)


def test_window_released_pages_are_never_read():
    """What the pool does behind a window: table slots wholly under a
    row's window name the null page, and every pool position no query
    may see holds NaN. The output is finite and equals the oracle's on
    the clean pool."""
    q_lens, kv_lens = EDGES["mixed_launch"]
    ops = _operands(q_lens, kv_lens, 8, seed=5)
    q, kp, vp, tbl, qs, ql, kl = ops
    want = _ref(ops, W)
    seen = np.zeros(kp.shape[1:3], bool)
    tbl = tbl.copy()
    for i, (n, m) in enumerate(zip(kl, ql)):
        if not m:
            tbl[i] = 0
            continue
        first = max(n - m - W + 1, 0)          # first key any query sees
        pos = np.arange(first, n)
        seen[tbl[i, pos // PS], pos % PS] = True
        tbl[i, :first // PS] = 0               # released
    kp = np.where(seen[None, :, :, None], kp, np.nan)
    vp = np.where(seen[None, :, :, None], vp, np.nan)
    out = _run((q, kp, vp, tbl, qs, ql, kl), 8, W)
    assert np.isfinite(out).all()
    _close(out, want, qs, ql)


@pytest.mark.parametrize("q_lens,kv_lens,window,want", [
    ([1], [40 * W], None, 4 * SLAB),            # 960 keys: four slabs
    ([1], [40 * W], W, SLAB),                   # 936..959 lie in slab 3
    ([1], [SLAB + 3], W, 2 * SLAB),             # window straddles a slab
    ([64], [64], W, 8 * SLAB),                  # eight q blocks, slab 0
    ([1, 16], [10, 2 * SLAB + 16], W, SLAB + 2 * 2 * SLAB),
])
def test_kv_tokens_read_follows_the_window(q_lens, kv_lens, window, want):
    got = ragged_kv_tokens_read(q_lens, kv_lens, q_block=8, page_size=PS,
                                pages_per_seq=PPS, window=window)
    assert got == want
