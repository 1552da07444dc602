"""Ragged gated delta rule (Pallas TPU): the recurrence of a Gated
DeltaNet layer over one serving step's packed rows.

Per value head, with a state ``S [dk, dv]`` a row carries from step to
step, token ``t`` of a row does (``g_t <= 0`` its log decay, ``beta_t`` in
``(0, 1)``, ``q_t`` and ``k_t`` L2-normalised, ``q_t`` scaled)::

    S <- exp(g_t) S
    u_t = beta_t (v_t - S^T k_t)
    S <- S + k_t u_t^T
    o_t = S^T q_t

:func:`gated_delta_reference` is that loop, token by token, in
``jax.numpy``. :func:`ragged_gated_delta` runs it for every row of a step
in one launch: a decode row's one token and a prefill chunk's many, each
row starting from its own SLOT of the state array (``serving/kv_cache.py``
owns the slots) and writing it back, or from zeros where the row's chunk
starts at position 0 (``fresh``: a new or recomputed row, whatever its
slot held).

The kernel's grid is ``(rows, head blocks)``. Queries, keys, values and
the gates stay in HBM and the body fetches the row's tokens itself; the
state of a (row, head block) is read once and written once, and the next
grid step's read flies while this one computes (three state buffers: one
being read, one computed on, one being written). A decode row takes the
loop above on the vector unit (no matrix product is worth its latency for
one token); a chunk of ``chunk`` tokens takes the chunked (WY / UT) form,
whose products are the MXU's::

    gam = cumsum(g)                  decays inside the chunk
    E_ij = exp(gam_i - gam_j), j <= i
    A = I + diag(beta) (E o K K^T) strictly below the diagonal
    U = A^-1 diag(beta) (V - diag(exp(gam)) K S0)
    O = diag(exp(gam)) Q S0 + (E o Q K^T) U
    S = exp(gam_C) S0 + (diag(exp(gam_C - gam)) K)^T U

``A = I + N`` is unit lower triangular. Its inverse is built by block
forward substitution, doubling the block: with the inverse of every
diagonal block of ``b`` tokens known (``inv_b``, block diagonal), the
blocks of ``2b`` are ``inv_2b = inv_b - inv_b N_b inv_b``, where ``N_b``
keeps the entries of ``N`` that join the two halves of a ``2b`` block.
Every factor is the inverse of a shorter run of the same recurrence, so
nothing grows before it cancels: a Neumann series ``(I - N)(I + N^2)...``
does, by many orders of magnitude, where the keys of a chunk are alike
(a repeated token) and the decay is slow. Every exponent is a difference
``gam_i - gam_j`` with ``j <= i``, so no factor exceeds 1 however
negative ``g`` is.

Tokens outside every row's ``q_len`` come back as garbage: the caller
masks them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
_HEADS_A_BLOCK = 8


def gated_delta_reference(q, k, v, g, beta, state):
    """The recurrence token by token, one row. ``q``/``k`` ``[n, H, dk]``,
    ``v`` ``[n, H, dv]``, ``g``/``beta`` ``[n, H]``, ``state [H, dk, dv]``
    (all float32). Returns ``(o [n, H, dv], state)``."""
    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[:, None, None]
        kv = jnp.einsum("hkv,hk->hv", s, kt, precision=HIGHEST)
        u = bt[:, None] * (vt - kv)
        s = s + kt[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt, precision=HIGHEST)

    state, o = jax.lax.scan(step, state.astype(F32),
                            (q.astype(F32), k.astype(F32), v.astype(F32),
                             g.astype(F32), beta.astype(F32)))
    return o, state


def _dot(a, b, dims=((1,), (0,))):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=HIGHEST,
                               preferred_element_type=F32)


def _lanes(x, n):
    """A ``[1, 1]`` value as a ``[1, n]`` row: the chip broadcasts along
    lanes and along sublanes, one at a time (the add keeps JAX from
    folding the two broadcasts into one)."""
    return x + jnp.zeros((1, n), x.dtype)


def _chunk_heads(q, k, v, gb, s_ref, o_ref, n, *, hb, dk, dv, chunk):
    """The chunked form over one chunk of ``chunk`` tokens, ``n`` of them
    live, for each of the ``hb`` heads: ``q``/``k``/``v`` ``[chunk, hb *
    d]`` buffers, ``gb [chunk, 128]`` (g of head h in lane h, beta in lane
    hb + h); ``s_ref [hb, dk, dv]`` the state, updated in place; ``o_ref
    [chunk, hb * dv]``."""
    c = chunk
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    live = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) < n
    incl, strict = row >= col, row > col
    eye = (row == col).astype(F32)
    lower = incl.astype(F32)
    # for halves of 1, 2, 4, ... tokens: the entries below the diagonal
    # that join the two halves of a block (row in the upper, column in the
    # lower half)
    joins, half = [], 1
    while half < c:
        block = 2 * half - 1
        joins.append(((row & half) != 0) & ((col & half) == 0)
                     & ((row | block) == (col | block)))
        half *= 2
    for h in range(hb):
        qh = jnp.where(live, q[:, h * dk:(h + 1) * dk], 0.0)
        kh = jnp.where(live, k[:, h * dk:(h + 1) * dk], 0.0)
        vh = jnp.where(live, v[:, h * dv:(h + 1) * dv], 0.0)
        g = jnp.where(live, gb[:, h:h + 1], 0.0)                  # [c, 1]
        b = jnp.where(live, gb[:, hb + h:hb + h + 1], 0.0)
        # the decays inside the chunk, as a column and as a row
        gam = _dot(lower, jnp.broadcast_to(g, (c, 128)))         # [c, 128]
        gam_col = gam[:, 0:1]
        gam_row = jnp.transpose(gam)[0:1, :]                      # [1, c]
        gam_last = gam[c - 1:c, 0:1]
        decay = jnp.exp(jnp.where(incl, gam_col - gam_row, -jnp.inf))
        kk = _dot(kh, kh, ((1,), (1,)))
        qk = _dot(qh, kh, ((1,), (1,)))
        nil = jnp.where(strict, b * decay * kk, 0.0)
        inv = eye - jnp.where(joins[0], nil, 0.0) if joins else eye
        for join in joins[1:]:
            inv = inv - _dot(inv, _dot(jnp.where(join, nil, 0.0), inv))
        s0 = s_ref[h]
        lead = jnp.exp(gam_col)
        u = _dot(inv, b * (vh - lead * _dot(kh, s0)))
        o_ref[:, h * dv:(h + 1) * dv] = lead * _dot(qh, s0) \
            + _dot(decay * qk, u)
        tail = jnp.exp(gam_last - gam_col)
        s_ref[h] = _lanes(jnp.exp(gam_last), dv) * s0 \
            + _dot(kh * tail, u, ((0,), (0,)))


def _token_heads(q, k, v, gb, s_ref, o_ref, *, hb, dk, dv):
    """One token (row 0 of ``q``/``k``/``v``/``gb``), each of the ``hb``
    heads, on the vector unit: the recurrence as written."""
    qt = jnp.transpose(q)                                      # [hb*dk, w]
    kt = jnp.transpose(k)
    for h in range(hb):
        qc = qt[h * dk:(h + 1) * dk, 0:1]                      # [dk, 1]
        kc = kt[h * dk:(h + 1) * dk, 0:1]
        s = s_ref[h] * _lanes(jnp.exp(gb[0:1, h:h + 1]), dv)
        kv = jnp.sum(s * kc, axis=0, keepdims=True)            # [1, dv]
        u = gb[0:1, hb + h:hb + h + 1] * (v[0:1, h * dv:(h + 1) * dv] - kv)
        s = s + kc * u
        s_ref[h] = s
        o_ref[0:1, h * dv:(h + 1) * dv] = jnp.sum(s * qc, axis=0,
                                                  keepdims=True)


def _kernel(slot_ref, qs_ref, ql_ref, fresh_ref, q_hbm, k_hbm, v_hbm,
            gb_hbm, s_in, o_hbm, s_out, s_buf, q_buf, k_buf, v_buf, gb_buf,
            o_buf, s_sem, in_sem, out_sem, *, hb, dk, dv, chunk, wb):
    r, j = pl.program_id(0), pl.program_id(1)
    nhb = pl.num_programs(1)
    steps = pl.num_programs(0) * nhb
    i = r * nhb + j

    def live(step):
        return ql_ref[step // nhb] > 0

    def reads(step):
        rr = step // nhb
        return (ql_ref[rr] > 0) & (fresh_ref[rr] == 0)

    def state_copy(step, out):
        rr, jj = step // nhb, step % nhb
        b = step % 3
        hbm = (s_out if out else s_in).at[slot_ref[rr], pl.ds(jj * hb, hb)]
        if out:
            return pltpu.make_async_copy(s_buf.at[b], hbm, s_sem.at[1, b])
        return pltpu.make_async_copy(hbm, s_buf.at[b], s_sem.at[0, b])

    @pl.when((i == 0) & reads(0))
    def _first():
        state_copy(0, False).start()

    # the buffer the next step reads into was written out two steps ago
    @pl.when((i >= 2) & live(jnp.maximum(i - 2, 0)))
    def _drained():
        state_copy(jnp.maximum(i - 2, 0), True).wait()

    nxt = jnp.minimum(i + 1, steps - 1)

    @pl.when((i + 1 < steps) & reads(nxt))
    def _prefetch():
        state_copy(nxt, False).start()

    n = ql_ref[r]
    q0 = qs_ref[r]
    lanes = (pl.ds(pl.multiple_of(j * hb * dk, hb * dk), hb * dk),
             pl.ds(pl.multiple_of(j * hb * dv, hb * dv), hb * dv))
    s_ref = s_buf.at[i % 3]

    def fetch(t0, rows):
        t0 = pl.multiple_of(t0, wb)
        copies = [
            pltpu.make_async_copy(src.at[pl.ds(t0, rows), lane],
                                  dst.at[pl.ds(0, rows)], in_sem.at[x])
            for x, (src, dst, lane) in enumerate(
                ((q_hbm, q_buf, lanes[0]), (k_hbm, k_buf, lanes[0]),
                 (v_hbm, v_buf, lanes[1])))]
        copies.append(pltpu.make_async_copy(
            gb_hbm.at[j, pl.ds(t0, rows)], gb_buf.at[pl.ds(0, rows)],
            in_sem.at[3]))
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    def write_out(t0, rows):
        """``rows`` live tokens of ``o_buf`` to the row's own slots, a
        ``wb``-token block at a time (a row owns whole blocks)."""
        blocks = pl.cdiv(rows, wb)

        def copy(b):
            return pltpu.make_async_copy(
                o_buf.at[pl.ds(pl.multiple_of(b * wb, wb), wb)],
                o_hbm.at[pl.ds(pl.multiple_of(t0 + b * wb, wb), wb),
                         lanes[1]], out_sem.at[0])

        def start(b, carry):
            copy(b).start()
            return carry

        def wait(b, carry):
            copy(b).wait()
            return carry

        jax.lax.fori_loop(0, blocks, start, None)
        jax.lax.fori_loop(0, blocks, wait, None)

    @pl.when(n > 0)
    def _row():
        @pl.when(fresh_ref[r] == 0)
        def _wait_state():
            state_copy(i, False).wait()

        @pl.when(fresh_ref[r] != 0)
        def _zero_state():
            s_ref[...] = jnp.zeros(s_ref.shape, F32)

        @pl.when(n == 1)
        def _decode():
            fetch(q0, wb)
            o_buf[pl.ds(0, wb)] = jnp.zeros((wb, hb * dv), F32)
            _token_heads(q_buf[pl.ds(0, wb)], k_buf[pl.ds(0, wb)],
                         v_buf[pl.ds(0, wb)], gb_buf[pl.ds(0, wb)], s_ref,
                         o_buf, hb=hb, dk=dk, dv=dv)
            write_out(q0, 1)

        @pl.when(n > 1)
        def _chunks():
            def body(c, carry):
                t0 = q0 + c * chunk
                left = jnp.minimum(n - c * chunk, chunk)
                fetch(t0, chunk)
                _chunk_heads(q_buf, k_buf, v_buf, gb_buf, s_ref, o_buf, left,
                             hb=hb, dk=dk, dv=dv, chunk=chunk)
                write_out(t0, left)
                return carry

            jax.lax.fori_loop(0, pl.cdiv(n, chunk), body, None)

        state_copy(i, True).start()

    @pl.when(i == steps - 1)
    def _drain():
        @pl.when((i >= 1) & live(jnp.maximum(i - 1, 0)))
        def _one_before():
            state_copy(jnp.maximum(i - 1, 0), True).wait()

        @pl.when(n > 0)
        def _this():
            state_copy(i, True).wait()


def ragged_gated_delta(q, k, v, g, beta, state, slots, q_starts, q_lens,
                       fresh, *, q_block=8, chunk=64, interpret=False):
    """The gated delta rule over a step's packed rows.

    q, k:    ``[T, Hk, dk]`` float32, L2-normalised (q scaled); a key head
        serves ``H // Hk`` value heads, consecutive.
    v:       ``[T, H, dv]`` float32.
    g, beta: ``[T, H]`` float32, the log decay (<= 0) and the write
        strength of every token and value head.
    state:   ``[slots, H, dk, dv]`` float32: every row's state, by slot.
    slots, q_starts, q_lens, fresh: ``[R]`` int32. Row ``r``'s tokens are
        ``q_starts[r] + [0, q_lens[r])``, starting at a multiple of
        ``q_block``; its state is ``state[slots[r]]``, or zeros where
        ``fresh[r]`` (its chunk starts at position 0). Pad rows carry
        ``q_len`` 0 and touch nothing.
    Returns ``(o [T, H, dv], state)``: the state of every live row
    written back to its slot, every other slot as it was.
    """
    t, hk, dk = q.shape
    h, dv = v.shape[1], v.shape[2]
    if h % hk:
        raise ValueError(f"{h} value heads do not group over {hk} key heads")
    if t % q_block:
        raise ValueError(f"{t} packed tokens are not whole q blocks of "
                         f"{q_block}")
    if not interpret and q_block % 8:
        raise ValueError(f"q_block {q_block}: the chip fetches and writes a "
                         f"row's float32 tokens by whole 8-row tiles")
    return _gated_delta_call(q, k, v, g, beta, state, slots, q_starts, q_lens,
                             fresh, q_block=q_block, chunk=int(chunk),
                             interpret=interpret)


@functools.partial(jax.jit, static_argnames=("q_block", "chunk", "interpret"))
def _gated_delta_call(q, k, v, g, beta, state, slots, q_starts, q_lens, fresh,
                      *, q_block, chunk, interpret):
    t, hk, dk = q.shape
    h, dv = v.shape[1], v.shape[2]
    hb = _HEADS_A_BLOCK if h % _HEADS_A_BLOCK == 0 else h
    nhb = h // hb
    wb = q_block
    chunk = max(-(-chunk // wb) * wb, wb)
    tp = t + chunk                   # a chunk read from any row stays inside

    def flat(x):                     # [T, H, d] -> [Tp, H * d] float32
        x = jnp.repeat(x, h // x.shape[1], axis=1) if x.shape[1] != h else x
        return jnp.pad(x.astype(F32).reshape(t, -1), ((0, tp - t), (0, 0)))

    gb = jnp.concatenate([g.astype(F32).reshape(t, nhb, hb),
                          beta.astype(F32).reshape(t, nhb, hb)], -1)
    gb = jnp.pad(jnp.transpose(gb, (1, 0, 2)),
                 ((0, 0), (0, tp - t), (0, 128 - 2 * hb)))   # [nhb, Tp, 128]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(slots.shape[0], nhb),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 5,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        scratch_shapes=[
            pltpu.VMEM((3, hb, dk, dv), F32),       # being read / used / written
            pltpu.VMEM((chunk, hb * dk), F32),
            pltpu.VMEM((chunk, hb * dk), F32),
            pltpu.VMEM((chunk, hb * dv), F32),
            pltpu.VMEM((chunk, 128), F32),
            pltpu.VMEM((chunk, hb * dv), F32),
            pltpu.SemaphoreType.DMA((2, 3)),
            pltpu.SemaphoreType.DMA((4,)),
            pltpu.SemaphoreType.DMA((1,)),
        ])
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb, dk=dk, dv=dv, chunk=chunk, wb=wb),
        out_shape=(jax.ShapeDtypeStruct((tp, h * dv), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)),
        grid_spec=grid_spec,
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name="ragged_gated_delta",
    )(slots.astype(jnp.int32), q_starts.astype(jnp.int32),
      q_lens.astype(jnp.int32), fresh.astype(jnp.int32),
      flat(q), flat(k), flat(v), gb, state.astype(F32))
    return o[:t].reshape(t, h, dv), state


__all__ = ["gated_delta_reference", "ragged_gated_delta"]
