"""Fused RMSNorm as a Pallas TPU kernel (forward + backward).

TPU-native rebuild of the reference's fused rms_norm
(paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu, surface
python/paddle/incubate/nn/functional/fused_rms_norm.py): one pass over the
rows computes the f32 moment + normalized output; backward fuses dx and the
cross-row dw reduction in a single sequential-grid kernel (the dw
accumulator lives in VMEM scratch across row blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 256


def _auto_block_rows(requested, f, n_f32_temps):
    """Largest row block whose f32 temporaries fit a ~6 MB VMEM budget."""
    budget = 6 * 1024 * 1024
    rows = budget // (4 * f * n_f32_temps)
    rows = max(8, 1 << (int(rows).bit_length() - 1)) if rows >= 8 else 8
    return min(requested, rows)


def _fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = x * rstd * w_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, w_ref, rstd_ref, dy_ref, dx_ref, dw_ref, dw_scr,
                *, nblocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    dxhat = dy * w
    dx = rstd * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True))
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dw_scr[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)

    @pl.when(i == nblocks - 1)
    def _finalize():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


def _run_fwd(x2, w, eps, block_rows, interpret):
    r, f = x2.shape
    block_rows = min(_auto_block_rows(block_rows, f, 3), r)
    nb = pl.cdiv(r, block_rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, f), x2.dtype),
            jax.ShapeDtypeStruct((r, 1), jnp.float32),
        ],
        interpret=interpret, name="rms_norm_fwd",
    )(x2, w.reshape(1, f))


def _run_bwd(x2, w, rstd, dy2, block_rows, interpret):
    r, f = x2.shape
    block_rows = min(_auto_block_rows(block_rows, f, 6), r)
    nb = pl.cdiv(r, block_rows)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, nblocks=nb),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, f), lambda i: (i, 0)),
            pl.BlockSpec((1, f), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, f), x2.dtype),
            jax.ShapeDtypeStruct((1, f), w.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, f), jnp.float32)],
        interpret=interpret, name="rms_norm_bwd",
    )(x2, w.reshape(1, f), rstd, dy2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rms(x, w, eps, block_rows, interpret):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y, _ = _run_fwd(x2, w, eps, block_rows, interpret)
    return y.reshape(shape)


def _rms_fwd(x, w, eps, block_rows, interpret):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y, rstd = _run_fwd(x2, w, eps, block_rows, interpret)
    return y.reshape(shape), (x2, w, rstd, shape)


def _rms_bwd(eps, block_rows, interpret, res, g):
    x2, w, rstd, shape = res
    dy2 = g.reshape(-1, shape[-1])
    dx, dw = _run_bwd(x2, w, rstd, dy2, block_rows, interpret)
    return dx.reshape(shape), dw.reshape(w.shape)


_rms.defvjp(_rms_fwd, _rms_bwd)


def _pick_block_rows(x, weight, epsilon, requested, interpret):
    """Route block_rows through the measured autotuner
    (kernels/autotune.py) when PADDLE_TPU_AUTOTUNE=1 — same winner-cache
    discipline as flash_attention. Under a trace only a cached winner is
    consulted; measurement needs concrete buffers."""
    from .autotune import autotune_enabled, pick_cached
    if not autotune_enabled():
        return requested
    f = x.shape[-1]
    rows = 1
    for d in x.shape[:-1]:
        rows *= int(d)
    cfg = pick_cached(
        key=("rms_norm", (rows, f), str(x.dtype), bool(interpret)),
        requested={"block_rows": requested},
        candidates=[{"block_rows": b} for b in (64, 128, 256, 512, 1024)
                    if b <= max(rows, 8)],
        build_fn=lambda c: (lambda: _run_fwd(
            x.reshape(-1, f), weight, float(epsilon), int(c["block_rows"]),
            bool(interpret))[0]),
        traced=isinstance(x, jax.core.Tracer)
        or isinstance(weight, jax.core.Tracer))
    return cfg["block_rows"]


def rms_norm(x, weight, epsilon=1e-6, block_rows=DEFAULT_BLOCK_ROWS,
             interpret=False):
    """Fused RMSNorm over the last axis. Differentiable (custom VJP)."""
    block_rows = _pick_block_rows(x, weight, epsilon, int(block_rows),
                                  bool(interpret))
    return _rms(x, weight, float(epsilon), int(block_rows), bool(interpret))
