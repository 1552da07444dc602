"""Fused multi-tensor AdamW bucket update as a Pallas TPU kernel.

One VMEM pass reads a dtype bucket's flat param/grad/moment buffers and
writes the updated param + both moments (the TPU rebuild of the fused
multi-tensor AdamW CUDA kernels behind the reference's
python/paddle/optimizer/fusion_utils.py). Callers are the fused optimizer
engine's flat buckets (optimizer/fused.py), which an EAGER ``opt.step()``
runs on one device: params f32 or bf16, moments f32. A compiled
``jit.TrainStep`` does not come here (it updates each leaf in its own
layout and sharding through the compiler's elementwise fusion), so the
kernel meets no mesh. The step-varying scalars (lr and the two bias
corrections) ride in SMEM so a changing lr/step never retraces;
betas/eps/weight_decay are compile-time constants. Block size is picked
by the measured autotuner
(kernels/autotune.py) when PADDLE_TPU_AUTOTUNE=1, and off-TPU callers get
a pure-jnp fallback with identical math.

Where the padding lives: the kernel runs over whole ``block_rows x 128``
chunks. The engine lays its bucket out ONCE at ``BUCKET_ALIGN``, a multiple
of every chunk the kernel may pick, and hands ``p``/``g``/``m``/``v`` at
that length, so ``_run``'s ``jnp.pad`` and ``[:n]`` emit nothing there
(``pad`` is 0, ``n`` is the whole). ``_run`` keeps both for callers that
hand it an unaligned array (this module's tests, one-off updates): those
pay four array-sized pads and three slices a call.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEFAULT_BLOCK_ROWS = 512  # 8 f32 row-buffers live at once: ~2 MB of VMEM
BLOCK_ROWS_CANDIDATES = (128, 256, 512, 1024)  # what the autotuner may pick
# every chunk the kernel may run over divides this: a flat buffer of a
# multiple of it needs no pad and no slice (optimizer/fused.py lays its
# buckets out at it)
BUCKET_ALIGN = max(BLOCK_ROWS_CANDIDATES) * LANES


def _kernel(sc_ref, p_ref, g_ref, m_ref, v_ref, po_ref, mo_ref, vo_ref, *,
            beta1, beta2, eps, wd, decoupled):
    lr = sc_ref[0, 0]
    c1 = sc_ref[0, 1]  # 1 - beta1**t
    c2 = sc_ref[0, 2]  # 1 - beta2**t
    g = g_ref[:].astype(jnp.float32)
    pf = p_ref[:].astype(jnp.float32)
    if wd and not decoupled:
        g = g + wd * pf
    m = beta1 * m_ref[:] + (1 - beta1) * g
    v = beta2 * v_ref[:] + (1 - beta2) * g * g
    u = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if wd and decoupled:
        u = u + wd * pf
    po_ref[:] = (pf - lr * u).astype(po_ref.dtype)
    mo_ref[:] = m
    vo_ref[:] = v


def _run(p, g, m, v, scalars, block_rows, interpret, *, beta1, beta2, eps,
         wd, decoupled):
    n = p.shape[0]
    chunk = block_rows * LANES
    pad = (-n) % chunk

    def as2d(a):
        return (jnp.pad(a, (0, pad)) if pad else a).reshape(-1, LANES)

    p2, g2, m2, v2 = as2d(p), as2d(g), as2d(m), as2d(v)
    rows = p2.shape[0]
    blk = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_kernel, beta1=beta1, beta2=beta2, eps=eps,
                          wd=wd, decoupled=decoupled),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((1, 3), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
            blk, blk, blk, blk,
        ],
        out_specs=[blk, blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), p.dtype),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        ],
        # in-place in HBM: an aligned bucket is updated where it lies (an
        # unaligned caller's padded copies are consumed by their outputs)
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interpret, name="fused_adamw",
    )(scalars, p2, g2, m2, v2)
    return (new_p.reshape(-1)[:n], new_m.reshape(-1)[:n],
            new_v.reshape(-1)[:n])


def _reference(p, g, m, v, lr, c1, c2, *, beta1, beta2, eps, wd, decoupled):
    """Pure-jnp fallback, math identical to the kernel (and to the eager
    per-param ``_adam_update``)."""
    g = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    if wd and not decoupled:
        g = g + wd * pf
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    u = (m / c1) / (jnp.sqrt(v / c2) + eps)
    if wd and decoupled:
        u = u + wd * pf
    return (pf - lr * u).astype(p.dtype), m, v


def fused_adamw(p, g, m, v, lr, t, *, beta1=0.9, beta2=0.999, eps=1e-8,
                weight_decay=0.0, decoupled=True,
                block_rows=DEFAULT_BLOCK_ROWS, interpret=False):
    """Flat AdamW/Adam bucket update: ``(new_p, new_m, new_v)``.

    ``p``/``g`` are 1-D f32 or bf16, ``m``/``v`` 1-D f32; ``lr`` and ``t``
    may be traced (they enter via SMEM scalars). The Pallas kernel engages
    on TPU or with ``interpret=True``; anything else takes the jnp body.
    """
    wd = float(weight_decay)
    c1 = 1 - beta1 ** t
    c2 = 1 - beta2 ** t
    from . import _on_tpu   # the shared cached backend probe
    if not (_on_tpu() or interpret):
        return _reference(p, g, m, v, lr, c1, c2, beta1=beta1, beta2=beta2,
                          eps=eps, wd=wd, decoupled=decoupled)
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32).reshape(()),
        jnp.asarray(c1, jnp.float32).reshape(()),
        jnp.asarray(c2, jnp.float32).reshape(()),
    ]).reshape(1, 3)
    kw = dict(beta1=beta1, beta2=beta2, eps=eps, wd=wd, decoupled=decoupled)

    def run(blocks):
        return _run(p, g, m, v, scalars, int(blocks), interpret, **kw)

    block_rows = _pick_block_rows(int(block_rows), p, run, interpret,
                                  decoupled)
    return run(block_rows)


def _pick_block_rows(requested, p, run_fn, interpret, decoupled):
    """Measured block-row selection with a per-(size, dtype) winner cache
    (the shared discipline in kernels/autotune.py)."""
    from .autotune import autotune_enabled, pick_cached
    if not autotune_enabled():
        return requested
    n = int(p.shape[0])
    cfg = pick_cached(
        key=("fused_adamw", n, str(p.dtype), bool(decoupled),
             bool(interpret)),
        requested={"block_rows": requested},
        candidates=[{"block_rows": b} for b in BLOCK_ROWS_CANDIDATES
                    if b * LANES <= max(n, 128 * LANES)],
        build_fn=lambda c: (lambda: run_fn(c["block_rows"])),
        traced=isinstance(p, jax.core.Tracer))
    return cfg["block_rows"]


def maybe_fused_adamw(p, g, m, v, lr, t, *, beta1, beta2, eps,
                      weight_decay, decoupled):
    """Kernel-tier gate for the fused optimizer engine: returns the update
    triple when the Pallas path applies (TPU backend, or
    PADDLE_TPU_FORCE_PALLAS=1 via the interpreter — how CPU CI exercises
    it), else None so the engine keeps its jnp bucket body. A kernel
    failure falls back the same way under FLAGS_enable_fusion_fallback."""
    forced = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
    from . import _on_tpu
    on_tpu = _on_tpu()
    if not (on_tpu or forced):
        return None
    try:
        return fused_adamw(p, g, m, v, lr, t, beta1=beta1, beta2=beta2,
                           eps=eps, weight_decay=weight_decay,
                           decoupled=decoupled,
                           interpret=forced and not on_tpu)
    except Exception:
        from ..core.flags import GLOBAL_FLAGS
        if GLOBAL_FLAGS.get("enable_fusion_fallback"):
            return None
        raise


__all__ = ["fused_adamw", "maybe_fused_adamw"]
