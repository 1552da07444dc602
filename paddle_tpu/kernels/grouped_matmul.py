"""Grouped matrix product (Pallas TPU): rows sorted by group, one weight
matrix a group — the expert product of a routed feed-forward layer.

``x [M, K]`` holds the rows of every group back to back, each group
starting at a multiple of the row tile ``tm`` (the caller pads; see
``nn/moe_dropless.py``), so a row tile belongs to ONE group and
``tile_group [M / tm]`` names it. ``w [G, K, N]``. Grid = (row tile,
column tile): a step multiplies one ``[tm, K]`` row tile by the ``[K,
tn]`` column block of its group's matrix, the whole contraction in one
block, so a group's matrix is read once a row tile it owns (once in all
while a group has at most ``tm`` rows: the weight-bound regime a serving
step lives in).

The buffer is sized for the worst routing; what a step routes here fills
its first ``num_live_tiles`` tiles. A tile past them costs a grid step
and nothing else: its index maps name the blocks the last live step
already holds (no DMA), its arithmetic is skipped, and it writes zeros
(a dead row is never NaN for whoever gathers by index later).

This is `jax.experimental.pallas.ops.tpu.megablox.gmm`'s problem cut to
what the serving path needs (forward only, padded groups, whole-K
blocks); it is its own ``pallas_call`` so that it carries a stable
``name=`` a trace reader can find.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "grouped_matmul"
_W_BLOCK_BYTES = 6 * 2 ** 20


def column_tile(k, n, itemsize):
    """Columns of a weight block: the widest multiple of 128 that
    divides ``n`` with the ``[k, tn]`` block within ~6 MiB (long DMAs,
    two of them in flight); all of ``n`` where it has no such divisor."""
    best = None
    for tn in range(128, n + 1, 128):
        if n % tn == 0 and k * tn * itemsize <= _W_BLOCK_BYTES:
            best = tn
    return best or n


def _kernel(tg_ref, live_ref, x_ref, w_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i < live_ref[0])
    def _live():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(i >= live_ref[0])
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(x, w, tile_group, num_live_tiles, *, tm=128,
                   interpret=False):
    """``out[r] = x[r] @ w[tile_group[r // tm]]`` for the rows of the
    first ``num_live_tiles`` row tiles, zeros for the rest.

    x: [M, K], M a multiple of ``tm``; w: [G, K, N]; tile_group:
    [M / tm] int32 in [0, G); num_live_tiles: int32 scalar. Returns
    [M, N] in x's dtype (f32 accumulation)."""
    m, k = x.shape
    g, kw, n = w.shape
    if kw != k:
        raise ValueError(f"grouped_matmul: x has K {k}, w has {kw}")
    if m % tm:
        raise ValueError(f"grouped_matmul: {m} rows are not whole tiles "
                         f"of {tm}")
    tn = column_tile(k, n, w.dtype.itemsize)
    m_tiles, n_tiles = m // tm, n // tn
    live = jnp.asarray(num_live_tiles, jnp.int32).reshape(1)

    def last_live(i, live_ref):
        return jnp.minimum(i, jnp.maximum(live_ref[0] - 1, 0))

    def x_map(i, j, tg_ref, live_ref):
        return (last_live(i, live_ref), 0)

    def w_map(i, j, tg_ref, live_ref):
        # a dead tile names the block the last live step left in VMEM
        col = jnp.where(i < live_ref[0], j, n_tiles - 1)
        return (tg_ref[last_live(i, live_ref)], 0, col)

    itemsize = x.dtype.itemsize
    vmem = 2 * (tm * k + tm * tn) * itemsize \
        + 2 * k * tn * w.dtype.itemsize + tm * tn * 4
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m_tiles, n_tiles),
            in_specs=[pl.BlockSpec((tm, k), x_map),
                      pl.BlockSpec((None, k, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, *_: (i, j))),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem * 1.25) + 4 * 2 ** 20),
        interpret=interpret, name=NAME,
    )(tile_group.astype(jnp.int32), live, x, w)


def grouped_matmul_reference(x, w, tile_group, num_live_tiles, *, tm=128):
    """jnp oracle: every row against its tile's matrix, in float32."""
    rows = jnp.arange(x.shape[0]) // tm
    wr = w[tile_group[rows]].astype(jnp.float32)          # [M, K, N]
    out = jnp.einsum("mk,mkn->mn", x.astype(jnp.float32), wr,
                     precision="highest")
    return jnp.where((rows < num_live_tiles)[:, None], out, 0.0) \
        .astype(x.dtype)


__all__ = ["NAME", "column_tile", "grouped_matmul",
           "grouped_matmul_reference"]
