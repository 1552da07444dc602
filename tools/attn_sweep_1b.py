"""Attention at 1B train shapes: XLA vs our flash vs jax splash.

Marginal-slope timing (two fori_loop lengths, readback sync) per
tools/perf_audit.py — cancels the fixed per-dispatch overhead.
Internal deadline; exits cleanly.
"""
import math
import time

T0 = time.time()
DEADLINE = 480.0

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from paddle_tpu.core.compile_cache import enable_compile_cache
from paddle_tpu.kernels.flash_attention import flash_attention as pflash

enable_compile_cache()


def timed_device(fn, x, iters, repeats=3):
    looped = jax.jit(lambda y: jnp.sum(lax.fori_loop(
        0, iters, lambda i, y: fn(y), y).astype(jnp.float32)))
    float(looped(x))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(looped(x))
        best = min(best, time.perf_counter() - t0)
    return best / iters


def marginal(fn, x):
    t3 = timed_device(fn, x, 3) * 3
    t13 = timed_device(fn, x, 13) * 13
    return (t13 - t3) / 10


S = 2048
for H, D in ((32, 64), (16, 128)):
    if time.time() - T0 > DEADLINE:
        print("deadline hit, exiting clean", flush=True)
        break
    HKV = 4
    G = H // HKV
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, H, S, D)) * 0.1, jnp.bfloat16)
    kv = jnp.asarray(rng.standard_normal((1, HKV, S, D)) * 0.1, jnp.bfloat16)

    def gqa_sdpa(q, kv=kv, G=G, HKV=HKV, D=D):
        qg = q.reshape(1, HKV, G, S, D)
        logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kv) / math.sqrt(D)
        m = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(m, logits, -1e9).astype(jnp.float32)
        p = jax.nn.softmax(logits, -1).astype(q.dtype)
        return jnp.einsum("bhgqk,bhkd->bhgqd", p, kv).reshape(q.shape)

    def fb(fn):
        return jax.grad(lambda q: jnp.sum(fn(q).astype(jnp.float32)))

    try:
        print(f"h{H} d{D} xla fwd+bwd: {marginal(fb(gqa_sdpa), q)*1e3:7.2f} ms",
              flush=True)
    except Exception as e:
        print(f"h{H} d{D} xla FAILED {type(e).__name__}: {e}"[:160], flush=True)
    for bq, bk in ((256, 512), (512, 512), (512, 1024)):
        if time.time() - T0 > DEADLINE:
            break
        try:
            t = marginal(fb(lambda q, bq=bq, bk=bk: pflash(
                q, kv, kv, causal=True, block_q=bq, block_k=bk)), q)
            print(f"h{H} d{D} ours bq{bq} bk{bk} fwd+bwd: {t*1e3:7.2f} ms",
                  flush=True)
        except Exception as e:
            print(f"h{H} d{D} ours bq{bq} FAILED {type(e).__name__}: {e}"[:160],
                  flush=True)
    # jax splash (production TPU kernel) — GQA-NATIVE via the MQA entry
    # (grouped K/V, no repeat), the same wrapper the step-level
    # PADDLE_TPU_ATTN_IMPL=splash path uses
    try:
        from paddle_tpu.kernels import splash_attention

        def run_splash(q, kv=kv):
            return splash_attention(q, kv, kv, causal=True)

        t = marginal(fb(run_splash), q)
        print(f"h{H} d{D} splash-gqa fwd+bwd: {t*1e3:7.2f} ms", flush=True)
    except Exception as e:
        print(f"h{H} d{D} splash FAILED {type(e).__name__}: {e}"[:200],
              flush=True)
print("DONE", flush=True)
