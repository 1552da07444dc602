"""Hand-tuned Pallas TPU kernels — the C12 tier of the reference.

The reference keeps 94k LoC of hand-fused CUDA kernels
(paddle/phi/kernels/fusion/gpu/) because torch-style eager execution cannot
fuse. On TPU most of that list is free: XLA fuses elementwise chains
(bias+act, residual+norm, rope, swiglu) into neighboring matmuls, so those
ops keep their composed jnp bodies (see nn/functional/*). Pallas kernels are
reserved for what XLA cannot do:

- ``flash_attention`` — online-softmax tiling so the [s, s] score matrix
  never materializes in HBM (reference CUDA kernel:
  paddle/phi/kernels/gpu/flash_attn_kernel.cu).
- ``rms_norm`` fused fwd+bwd over rows (reference:
  paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu).
- ring attention (paddle_tpu/distributed, built on the same inner kernel).

``install()`` registers the overrides into the eager op registry
unconditionally and backend-free; each override decides per call whether
the Pallas path applies (TPU backend, or PADDLE_TPU_FORCE_PALLAS=1 which
uses the Pallas interpreter — how the CPU CI tests these kernels).
"""
from __future__ import annotations

import os

import jax

from .decode_megakernel import fused_decode_layer as pallas_decode_layer
from .flash_attention import flash_attention as pallas_flash_attention
from .fused_adamw import fused_adamw as pallas_fused_adamw
from .int8_matmul import dequant_matmul as pallas_dequant_matmul
from .rms_norm import rms_norm as pallas_rms_norm


_ON_TPU = None  # tri-state cache; resolved on first kernel call, NOT at import

_SPLASH_KERNELS = {}  # cache key -> compiled splash kernel


def splash_attention(q, k, v, causal=True, scale=None, interpret=False):
    """jax's production TPU splash-attention kernel over [b, h, s, d]
    inputs. GQA is NATIVE: grouped key/value ride the MQA kernel vmapped
    over kv heads — K/V are never repeated, so a 32/4-head model moves
    8x less K/V HBM than the repeat-to-MHA formulation. Per-shape
    kernels are cached; ``interpret=True`` runs the Pallas interpreter
    (CPU numerics tests). Selected by PADDLE_TPU_ATTN_IMPL=splash for
    the step-level attention A/B."""
    import math

    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as _sk,
        splash_attention_mask as _sm,
    )

    b, h, sq, d = q.shape
    skv = k.shape[2]
    hkv = k.shape[1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    def _mask(n_heads):
        mk = (_sm.CausalMask((sq, skv)) if causal
              else _sm.FullMask((sq, skv)))
        return _sm.MultiHeadMask([mk for _ in range(n_heads)])

    if hkv != h:
        g = h // hkv
        key = ("mqa", g, sq, skv, bool(causal), interpret)
        kernel = _SPLASH_KERNELS.get(key)
        if kernel is None:
            kernel = _sk.make_splash_mqa_single_device(
                mask=_mask(g), interpret=interpret)
            _SPLASH_KERNELS[key] = kernel
        qg = q.reshape(b, hkv, g, sq, d)
        out = jax.vmap(jax.vmap(
            lambda qq, kk, vv: kernel(qq * s, kk, vv)))(qg, k, v)
        return out.reshape(b, h, sq, d)
    key = ("mha", h, sq, skv, bool(causal), interpret)
    kernel = _SPLASH_KERNELS.get(key)
    if kernel is None:
        kernel = _sk.make_splash_mha(mask=_mask(h), head_shards=1,
                                     q_seq_shards=1, interpret=interpret)
        _SPLASH_KERNELS[key] = kernel
    return jax.vmap(lambda qq, kk, vv: kernel(qq * s, kk, vv))(q, k, v)


def _on_tpu() -> bool:
    # Touching jax.devices() initializes the backend — must never run at
    # import time (import paddle_tpu stays backend-free); install() defers
    # this check to the first kernel call. A backend that fails to come up
    # raises here: "no TPU" is an answer only jax.devices() itself gives.
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.devices()[0].platform not in ("cpu", "gpu")
    return _ON_TPU


def flash_threshold(forced: bool = False) -> int:
    """Sequence length from which the sdpa override engages the Pallas
    flash kernel: PADDLE_TPU_FLASH_THRESHOLD, else (unless the kernel is
    forced, where it is 256) FLAGS_pallas_flash_threshold, else 8192.
    Read per call so tests/fixtures can flip the gates after import."""
    env = os.environ.get("PADDLE_TPU_FLASH_THRESHOLD")
    if env is not None:
        return int(env)
    if forced:
        return 256
    from ..core.flags import GLOBAL_FLAGS
    flag = GLOBAL_FLAGS.get("pallas_flash_threshold")
    return int(flag) if flag is not None else 8192


def install():
    """Override eager op bodies with Pallas kernels where profitable.

    Registration is unconditional and backend-free; each override decides
    lazily (first call, cached) whether the Pallas path applies, so that
    ``import paddle_tpu`` never initializes a JAX backend.
    """
    from ..core.dispatch import override_kernel
    from ..nn.functional.attention import _sdpa_reference

    def sdpa(q, k, v, *rest, causal=False, dropout_p=0.0, scale=None,
             dropout_key=None):
        attn_mask = rest[0] if rest else None
        # Env gates are read per call so tests/fixtures can flip them after
        # import; the backend probe is cached after the first call.
        forced = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
        # PADDLE_TPU_ATTN_IMPL: step-level attention A/B selector
        # (round-5): auto (default tiering) | xla (pin the composition) |
        # flash (pin our Pallas kernel) | splash (pin jax's production
        # TPU splash-attention kernel).
        impl = os.environ.get("PADDLE_TPU_ATTN_IMPL", "auto")
        if impl == "xla":
            return _sdpa_reference(q, k, v, *rest, causal=causal,
                                   dropout_p=dropout_p, scale=scale,
                                   dropout_key=dropout_key)
        # splash engages on TPU, or off-TPU only under the explicit
        # interpreter opt-in (numerics tests) — a pinned launch config
        # carried onto a CPU/GPU dev box must fall through to native-
        # speed tiers, not silently run interpreter-mode attention
        splash_ok = _on_tpu() or \
            os.environ.get("PADDLE_TPU_SPLASH_INTERPRET") == "1"
        if impl == "splash" and splash_ok and attn_mask is None \
                and dropout_p == 0.0:
            import jax.numpy as jnp
            try:
                out = splash_attention(
                    jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                    jnp.swapaxes(v, 1, 2), causal=causal, scale=scale,
                    interpret=not _on_tpu())
                return jnp.swapaxes(out, 1, 2)
            except Exception:
                from ..core.flags import GLOBAL_FLAGS
                if not GLOBAL_FLAGS.get("enable_fusion_fallback"):
                    raise
                from ..core.vlog import vlog
                vlog(0, "splash attention failed; falling back to the "
                        "XLA composition")
                return _sdpa_reference(q, k, v, *rest, causal=causal,
                                       dropout_p=dropout_p, scale=scale,
                                       dropout_key=dropout_key)
        if impl == "flash":
            forced = True        # pin the Pallas kernel (interpret off-TPU)
        use_pallas = forced or _on_tpu()
        interpret = not _on_tpu()
        # Measured on a v5e chip in round 2 (scan-chained fwd+bwd, readback
        # sync; b=8 h=12 d=64): XLA composition beats every Pallas kernel
        # tried (ours, jax flash, splash) up to s=4096 — e.g. s=2048 XLA
        # 14.4ms vs Pallas 32.7ms; engaging Pallas at s=2048 cost 2.3x
        # end-to-end train MFU (0.39 -> 0.18). Mosaic kernels run far below
        # roofline on this part, so the threshold defaults to 8192 — where
        # the O(s^2) score materialization starts to dominate/ OOM and the
        # O(s) working set is worth it regardless. Tunable per deployment
        # via PADDLE_TPU_FLASH_THRESHOLD (re-measure on real v5p/v5e metal).
        thresh = flash_threshold(forced)
        # Pallas path: no arbitrary mask, no dropout, seq long enough to
        # beat the fused XLA composition.
        from ..core.flags import GLOBAL_FLAGS
        # FLAGS_flash_attn_version: 1 pins the composed XLA body (the
        # reference's FA1/FA2 selector; here "1" = no flash tier), 2 = the
        # Pallas flash kernel tier (default).
        _ver = GLOBAL_FLAGS.get("flash_attn_version")
        version_ok = int(_ver if _ver is not None else 2) >= 2
        if use_pallas and version_ok and attn_mask is None \
                and dropout_p == 0.0 and q.shape[1] >= thresh \
                and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0:
            import jax.numpy as jnp
            qh = jnp.swapaxes(q, 1, 2)  # paddle [b,s,h,d] -> kernel [b,h,s,d]
            kh = jnp.swapaxes(k, 1, 2)
            vh = jnp.swapaxes(v, 1, 2)
            try:
                out = pallas_flash_attention(qh, kh, vh, causal=causal,
                                             scale=scale, interpret=interpret)
                return jnp.swapaxes(out, 1, 2)
            except Exception:
                # FLAGS_enable_fusion_fallback (reference flags.cc): a
                # failing fused kernel falls back to the composed body
                # instead of killing the step; off = surface the error.
                if not GLOBAL_FLAGS.get("enable_fusion_fallback"):
                    raise
                from ..core.vlog import vlog
                vlog(0, "pallas flash_attention failed; falling back to "
                        "the XLA composition (FLAGS_enable_fusion_fallback)")
        return _sdpa_reference(q, k, v, *rest, causal=causal,
                               dropout_p=dropout_p, scale=scale,
                               dropout_key=dropout_key)

    override_kernel("scaled_dot_product_attention", sdpa)

    # rms_norm: measured on v5e the XLA fusion matches the Pallas kernel
    # (6.8ms vs 7.0ms fwd+bwd at [8192, 4096]) — XLA keeps the default.
    # The kernel stays available (and tested) for stacks where the fusion
    # regresses; opt in via PADDLE_TPU_PALLAS_RMSNORM=1 (read per call).
    def rms(x, *rest, epsilon=1e-6):
        weight = rest[0] if rest else None
        forced = os.environ.get("PADDLE_TPU_FORCE_PALLAS") == "1"
        enabled = forced or os.environ.get("PADDLE_TPU_PALLAS_RMSNORM") == "1"
        if enabled and (forced or _on_tpu()) and weight is not None \
                and x.shape[-1] % 128 == 0 and x.ndim >= 2:
            return pallas_rms_norm(x, weight, epsilon=epsilon,
                                   interpret=not _on_tpu())
        from ..nn.functional.norm import _rms_norm_reference
        return _rms_norm_reference(x, *rest, epsilon=epsilon)

    override_kernel("rms_norm", rms)
    return True


__all__ = ["pallas_flash_attention", "pallas_rms_norm",
           "pallas_fused_adamw", "pallas_dequant_matmul", "install"]
