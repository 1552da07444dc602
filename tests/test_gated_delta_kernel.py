"""``kernels/gated_delta.py::ragged_gated_delta`` (interpreted) against its
token-by-token recurrence, ``gated_delta_reference``.

Sizes: 4 value heads over 2 key heads, heads of 16, chunks of 8 tokens,
q blocks of 4, 6 state slots. Tolerance 2e-5 on outputs and states of
size 1: both sides are float32 at ``highest``; the chunked form sums in
another order, and its inverse of ``I + N`` is a triangular solve whose
factors stay bounded. A wrong decay, a dropped ``beta`` or a state not
carried moves them by 1e-2 and more."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels.gated_delta import (gated_delta_reference,
                                            ragged_gated_delta)

H, HK, D, QB, CHUNK, SLOTS = 4, 2, 16, 4, 8, 6
TOL = 2e-5


def _tokens(rng, n, g_scale=1.0):
    """One row's ``n`` tokens as the layer hands them over: q and k
    L2-normalised per key head (q scaled), g <= 0, beta in (0, 1)."""
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((n, HK, D))) * D ** -0.5
    k = unit(rng.standard_normal((n, HK, D)))
    v = rng.standard_normal((n, H, D))
    g = -g_scale * np.log1p(np.exp(rng.standard_normal((n, H))))
    beta = 1 / (1 + np.exp(-rng.standard_normal((n, H))))
    return [np.asarray(x, np.float32) for x in (q, k, v, g, beta)]


def _launch(rows, state, T=64, R=5, chunk=CHUNK):
    """Pack ``rows`` = [(slot, tokens, fresh)] and run one launch."""
    q = np.zeros((T, HK, D), np.float32)
    k, v = np.zeros_like(q), np.zeros((T, H, D), np.float32)
    g, beta = np.zeros((T, H), np.float32), np.zeros((T, H), np.float32)
    slots = np.zeros((R,), np.int32)
    q_starts = np.full((R,), T, np.int32)
    q_lens = np.zeros((R,), np.int32)
    fresh = np.zeros((R,), np.int32)
    cursor = 0
    for r, (slot, toks, is_fresh) in enumerate(rows):
        n = len(toks[0])
        for buf, x in zip((q, k, v, g, beta), toks):
            buf[cursor:cursor + n] = x
        slots[r], q_starts[r], q_lens[r], fresh[r] = slot, cursor, n, is_fresh
        cursor += -(-n // QB) * QB
    assert cursor <= T
    o, state = ragged_gated_delta(
        *(jnp.asarray(x) for x in (q, k, v, g, beta)), state,
        jnp.asarray(slots), jnp.asarray(q_starts), jnp.asarray(q_lens),
        jnp.asarray(fresh), q_block=QB, chunk=chunk, interpret=True)
    outs = [np.asarray(o[q_starts[r]:q_starts[r] + q_lens[r]])
            for r in range(len(rows))]
    return outs, state


def _reference(toks, s0):
    q, k, v, g, beta = (jnp.asarray(x) for x in toks)
    rep = H // HK
    return gated_delta_reference(jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1),
                                 v, g, beta, s0)


def _state(seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (SLOTS, H, D, D)) * 0.3, jnp.float32)


def _check(rows, state, T=64):
    outs, new = _launch(rows, state, T=T)
    touched = set()
    for (slot, toks, fresh), got in zip(rows, outs):
        s0 = jnp.zeros((H, D, D)) if fresh else state[slot]
        want_o, want_s = _reference(toks, s0)
        np.testing.assert_allclose(got, want_o, atol=TOL, rtol=TOL)
        np.testing.assert_allclose(new[slot], want_s, atol=TOL, rtol=TOL)
        touched.add(slot)
    for s in set(range(SLOTS)) - touched:     # every other slot as it was
        np.testing.assert_array_equal(new[s], state[s])
    return outs, new


def test_decode_and_prefill_rows_in_one_launch():
    rng = np.random.default_rng(0)
    rows = [(3, _tokens(rng, 1), 0),           # decode row from its slot
            (1, _tokens(rng, 20), 1),          # a fresh prompt's first chunk
            (5, _tokens(rng, 1), 0),
            (2, _tokens(rng, 13), 0)]          # a later chunk, from its slot
    _check(rows, _state(1))


def test_a_chunk_that_ends_mid_block():
    """11 tokens: a whole chunk of 8, then 3 of a q block of 4."""
    rng = np.random.default_rng(2)
    _check([(4, _tokens(rng, 11), 0), (0, _tokens(rng, 1), 0)], _state(3))


def test_a_prompt_that_spans_three_steps():
    """A 29-token prompt prefilled 12 + 12 + 5 over three launches, its
    state carried in its slot, then two decode steps: the same outputs
    and state as the recurrence over all 31 tokens at once."""
    rng = np.random.default_rng(4)
    toks = _tokens(rng, 31)
    state = _state(5)
    got, cuts = [], [(0, 12), (12, 24), (24, 29), (29, 30), (30, 31)]
    for a, b in cuts:
        part = [x[a:b] for x in toks]
        outs, state = _launch([(2, part, int(a == 0))], state)
        got.append(outs[0])
    want_o, want_s = _reference(toks, jnp.zeros((H, D, D)))
    np.testing.assert_allclose(np.concatenate(got), want_o, atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(state[2], want_s, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("n", [1, 7, 24])
def test_one_row_alone(n):
    rng = np.random.default_rng(6 + n)
    _check([(1, _tokens(rng, n), 0)], _state(7))


def test_strongly_negative_decay():
    """g down to -60: exp(g) underflows to 0 inside a chunk; a factor
    exp(-cumsum) would overflow, the differences the kernel takes do
    not."""
    rng = np.random.default_rng(8)
    rows = [(1, _tokens(rng, 17, g_scale=40.0), 0),
            (3, _tokens(rng, 1, g_scale=40.0), 0)]
    outs, new = _check(rows, _state(9))
    assert all(np.isfinite(o).all() for o in outs)
    assert np.isfinite(np.asarray(new)).all()


@pytest.mark.parametrize("beta", [0.5, 0.9, 0.99])
def test_one_key_repeated_over_a_whole_chunk(beta):
    """A token repeated 64 times (pasted text, code) gives the same key
    throughout a chunk of 64; with beta near 1 and almost no decay the
    powers of ``N`` reach 1e9 and more, so an inverse that sums them
    (a Neumann series) misses by as much. A triangular solve does not."""
    rng = np.random.default_rng(12)
    q, _, v, _, _ = _tokens(rng, 64)
    key = rng.standard_normal(D)
    k = np.broadcast_to(key / np.linalg.norm(key), (64, HK, D))
    g = np.full((64, H), -1e-3, np.float32)
    toks = [q, np.asarray(k, np.float32), v, g,
            np.full((64, H), beta, np.float32)]
    state = _state(13)
    outs, new = _launch([(4, toks, 0)], state, T=72, chunk=64)
    want_o, want_s = _reference(toks, state[4])
    np.testing.assert_allclose(outs[0], want_o, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(new[4], want_s, atol=TOL, rtol=TOL)


def test_the_comparison_has_power():
    """The same launch against a recurrence without the decay, and
    without beta, misses by far more than the tolerance."""
    rng = np.random.default_rng(10)
    toks = _tokens(rng, 12)
    state = _state(11)
    outs, _ = _launch([(0, toks, 0)], state)
    for i, wrong in ((3, np.zeros_like(toks[3])), (4, np.ones_like(toks[4]))):
        bad = list(toks)
        bad[i] = wrong
        want, _ = _reference(bad, state[0])
        assert np.abs(outs[0] - np.asarray(want)).max() > 1e-2
