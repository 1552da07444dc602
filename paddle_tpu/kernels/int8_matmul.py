"""Fused dequant-matmul for weight-only low-bit serving (Pallas TPU).

Reference capability being matched: weight_only_linear int8/int4
(paddle/phi/kernels/gpu/weight_only_linear_kernel.cu) — the decode-path
matmul whose weight lives in HBM at 1/4 (int8) or 1/8 (int4) of the fp32
bandwidth and is dequantized *in the matmul prologue*, never materialized
as a full-precision array in HBM. Decode throughput is memory-bandwidth
bound (PAPER/EQuARX bandwidth math), so the weight bytes moved per token
are the metric this kernel exists to cut.

Layout contract (matches quantization.quantize_to_int8/int4):
- ``w_q [K, N] int8`` quantized per OUT channel (axis 1): one fp32 scale
  per column, ``scale [1, N]``;
- int4: ``w_packed [ceil(K/2), N] int8`` with two nibbles per byte packed
  along the contraction axis (row ``2r`` in the low nibble, ``2r+1`` in
  the high nibble), same per-column scale.

Kernel shape: grid (M/bm, N/bn, K/bk) with the K axis innermost and
sequential; a VMEM f32 scratch tile carries the partial product. The
weight tile is dequantized on arrival — ``w_q.astype(f32) * scale`` (the
prologue) — and rides one MXU dot per (m, n, k) step. Per-column scales
ship as a (1, bn) block; they are vector operands of the prologue multiply,
so they live in VMEM (TPU SMEM is scalar memory — vector reads do not
lower; the fused_adamw kernel's SMEM scalars are the pattern for *scalar*
step inputs, not per-channel vectors).

Block sizes are picked by the measured autotuner (kernels/autotune.py)
under PADDLE_TPU_AUTOTUNE=1, per (M, K, N, bits) key. Off-TPU callers get
a pure-jnp fallback with identical math (and the interpret path under
PADDLE_TPU_FORCE_PALLAS=1 — how CPU CI exercises the kernel body).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = {"bm": 128, "bn": 128, "bk": 512}

# Eager-dispatch forensics for the decode gate
# (tests/test_quantized_path.py): a fully-jitted decode calls this module
# only under a trace, so the eager counter must stay flat across tokens —
# a per-token eager dequant dispatch is exactly the regression the gate
# exists to catch (the optimizer/serving dispatch-gate discipline).
_EAGER_DISPATCH = {"count": 0}


def eager_dispatch_count() -> int:
    return _EAGER_DISPATCH["count"]


def _record_eager(*arrays):
    if not any(isinstance(a, jax.core.Tracer) for a in arrays):
        _EAGER_DISPATCH["count"] += 1


def _kernel_int8(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                    # [bm, bk]
    # prologue dequant: the weight tile becomes fp only inside VMEM
    w = w_ref[...].astype(jnp.float32) * s_ref[...]       # [bk, bn]*[1, bn]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_int4(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Nibbles sign-extend through i32 shifts (Mosaic legalizes neither
    # i8 vector shifts nor the sublane interleave that would put rows 2r
    # and 2r+1 back in contraction order). The interleave is not needed:
    # x @ W = x[:, 0::2] @ W_lo + x[:, 1::2] @ W_hi, and the caller ships
    # x already split by column parity.
    p = w_ref[...].astype(jnp.int32)                      # [bk//2, bn]
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)       # rows 2r
    hi = jnp.right_shift(jnp.left_shift(p, 24), 28)       # rows 2r+1
    sc = s_ref[...]

    def dot(x_ref, w):
        return jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w.astype(jnp.float32) * sc,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    acc_ref[...] += dot(xe_ref, lo) + dot(xo_ref, hi)

    @pl.when(k == n_k - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pallas_matmul(x2, w_q, scale, rows, bits, bm, bn, bk, interpret):
    """x2 [M, K] fp; w_q int8 ([K, N] or packed [K/2, N]); scale [1, N]."""
    m, k_dim = x2.shape
    n = w_q.shape[1]
    pad_m = (-m) % bm
    pad_k = (-k_dim) % bk
    pad_n = (-n) % bn
    xp = jnp.pad(x2, ((0, pad_m), (0, pad_k))) if (pad_m or pad_k) else x2
    if bits == 8:
        wp = jnp.pad(w_q, ((0, pad_k), (0, pad_n))) if (pad_k or pad_n) \
            else w_q
        kernel, w_rows_per_bk = _kernel_int8, bk
        xs = [xp]
    else:
        # packed rows = K/2; zero nibbles dequantize to 0 so K padding is
        # safe (pad_k is even because bk is)
        w_rows = (k_dim + pad_k) // 2
        wp = jnp.pad(w_q, ((0, w_rows - w_q.shape[0]), (0, pad_n))) \
            if (w_rows != w_q.shape[0] or pad_n) else w_q
        kernel, w_rows_per_bk = _kernel_int4, bk // 2
        xs = [xp[:, 0::2], xp[:, 1::2]]
    sp = jnp.pad(scale.reshape(1, -1), ((0, 0), (0, pad_n))) if pad_n \
        else scale.reshape(1, -1)
    grid = ((m + pad_m) // bm, (n + pad_n) // bn, (k_dim + pad_k) // bk)
    x_spec = pl.BlockSpec((bm, bk // len(xs)), lambda i, j, k: (i, k),
                          memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(kernel, n_k=grid[2]),
        grid=grid,
        in_specs=[x_spec] * len(xs) + [
            pl.BlockSpec((w_rows_per_bk, bn), lambda i, j, k: (k, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m + pad_m, n + pad_n), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="dequant_matmul",
    )(*xs, wp, sp)
    return out[:m, :n]


def _reference(x2, w_q, scale, rows, bits):
    """Pure-jnp fallback, math identical to the kernel (parity-tested)."""
    if bits == 8:
        w = w_q.astype(jnp.float32)
    else:
        from ..quantization import unpack_int4
        w = unpack_int4(w_q, rows).astype(jnp.float32)
    w = w * scale.reshape(1, -1)
    return jax.lax.dot_general(
        x2.astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x2.dtype)


def _pick_blocks(m, k_dim, n, bits, run_fn, traced):
    from .autotune import autotune_enabled, pick_cached
    if not autotune_enabled():
        return DEFAULT_BLOCK
    cands = [
        {"bm": bm, "bn": bn, "bk": bk}
        for bm in (128, 256) for bn in (128, 256, 512)
        for bk in (256, 512, 1024)
        if bm <= max(m, 128) * 2 and bn <= max(n, 128) * 2
        and bk <= max(k_dim, 256) * 2
    ] or [DEFAULT_BLOCK]
    return pick_cached(
        key=("int8_matmul", m, k_dim, n, bits),
        requested=DEFAULT_BLOCK,
        candidates=cands,
        build_fn=lambda c: (lambda: run_fn(c)),
        traced=traced)


def dequant_matmul(x, w_q, scale, *, rows=None, bits=8, interpret=None):
    """``x @ dequant(w_q)`` with per-out-channel scales.

    x: [..., K] float; w_q: [K, N] int8 (bits=8) or nibble-packed
    [ceil(K/2), N] int8 (bits=4); scale: broadcastable to [1, N] fp32.
    Returns [..., N] in x's dtype. The Pallas kernel engages on TPU (or
    under PADDLE_TPU_FORCE_PALLAS=1 via the interpreter); anything else
    takes the jnp fallback with identical math.
    """
    if bits not in (8, 4):
        raise ValueError(f"bits must be 8 or 4, got {bits}")
    if rows is None:
        if bits == 4:
            raise ValueError("int4 needs rows= (the unpacked K)")
        rows = w_q.shape[0]
    _record_eager(x, w_q, scale)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    forced = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
    from . import _on_tpu   # the shared cached backend probe
    on_tpu = _on_tpu()
    if interpret is None:
        interpret = forced and not on_tpu
    use_pallas = on_tpu or interpret
    n = w_q.shape[1]
    if use_pallas:
        m, k_dim = x2.shape

        def run(cfg):
            bm = min(cfg["bm"], 512)
            bk = cfg["bk"]
            if bits == 4 and bk % 2:
                bk += 1
            return _pallas_matmul(x2, w_q, scale, rows, bits,
                                  bm, cfg["bn"], bk, interpret)

        traced = any(isinstance(a, jax.core.Tracer) for a in (x2, w_q))
        cfg = _pick_blocks(m, k_dim, n, bits, run, traced)
        try:
            out = run(cfg)
        except Exception:
            from ..core.flags import GLOBAL_FLAGS
            if not GLOBAL_FLAGS.get("enable_fusion_fallback"):
                raise
            from ..core.vlog import vlog
            vlog(0, "pallas int8_matmul failed; falling back to the jnp "
                    "dequant body (FLAGS_enable_fusion_fallback)")
            out = _reference(x2, w_q, scale, rows, bits)
    else:
        out = _reference(x2, w_q, scale, rows, bits)
    return out.reshape(lead + (n,))


__all__ = ["dequant_matmul", "eager_dispatch_count"]
