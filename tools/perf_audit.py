"""MFU audit on the real chip (round-2 verdict 'weak #1').

Measures, and writes to docs/PERF_AUDIT.json for PERF.md:
  1. pure-matmul roofline: best sustained bf16 TF/s over square matmuls —
     the practical ceiling the MFU denominator should be read against;
  2. attention path comparison: XLA composed SDPA vs the Pallas flash
     kernel across sequence lengths (the autotune threshold's evidence);
  3. train-step decomposition on the bench config: forward, forward+
     backward, full fused step (fwd+bwd+AdamW), with achieved model TF/s.

Run: python tools/perf_audit.py  (one process on the chip at a time.)
"""
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def timed(fn, *args, iters=10, warmup=2):
    """Per-iteration sync. Use only when per-call work >> dispatch RTT."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def timed_chain(fn, x, iters=20, warmup=2):
    """Chained timing: fn maps x -> same-shape array; each call consumes the
    previous output, so async dispatch cannot overlap/elide the work
    being measured."""
    import jax
    y = x
    for _ in range(warmup):
        y = fn(y)
    jax.block_until_ready(y)
    y = x
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(y)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters


def timed_device(fn, x, iters=20, repeats=3):
    """Pure on-device time: ONE dispatch running ``iters`` chained
    applications of ``fn`` inside a lax.fori_loop, reduced to a scalar that
    is READ BACK — a value on the host closes the timing whatever the
    runtime's ``block_until_ready`` does.
    Min over ``repeats`` (the fixed per-dispatch overhead varies
    run-to-run); use the marginal between two loop lengths to cancel it."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    looped = jax.jit(lambda y: jnp.sum(lax.fori_loop(
        0, iters, lambda i, y: fn(y), y).astype(jnp.float32)))
    float(looped(x))  # compile + run
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(looped(x))
        best = min(best, time.perf_counter() - t0)
    return best / iters


def matmul_roofline(peak_tflops=197.0):
    import jax
    import jax.numpy as jnp
    out = []
    for n in (2048, 4096, 8192):
        try:
            a = jnp.asarray(np.random.default_rng(0).standard_normal(
                (n, n)) * 0.01, jnp.bfloat16)
            b = jnp.asarray(np.random.default_rng(1).standard_normal(
                (n, n)) * 0.01, jnp.bfloat16)
            # marginal cost between two in-device loop lengths — subtracts
            # the fixed dispatch+sync overhead exactly.
            # Round-3 verdict weak #2: at small n the per-iter time is
            # ~0.1 ms, so a 50-iteration marginal sat inside timing noise
            # and reported > nominal peak (202.5 > 197 TF/s, impossible).
            # Scale the iteration GAP so the marginal work is >= 200 ms of
            # expected compute at peak — noise then bounds the error at
            # a few percent.
            per_iter_at_peak = 2 * n ** 3 / (peak_tflops * 1e12)
            gap = max(int(0.2 / per_iter_at_peak), 20)
            gap = min(gap, 2400)   # compile-time guard at tiny n
            lo, hi = 5, 5 + gap
            # tanh between iterations defeats XLA's reassociation of the
            # matmul chain into log-depth matrix powers (measured: the pure
            # y@b loop reports >2x nominal peak — it is NOT executing k
            # matmuls)
            body = lambda x, b=b: jnp.tanh(x @ b)  # noqa: E731
            t5 = timed_device(body, a, iters=lo) * lo
            t45 = timed_device(body, a, iters=hi) * hi
            dt = (t45 - t5) / (hi - lo)
            tf = 2 * n ** 3 / dt / 1e12
            rec = {"n": n, "iters": (lo, hi), "ms": round(dt * 1e3, 3),
                   "tflops": round(tf, 1),
                   "fixed_dispatch_ms": round((t5 - lo * dt) * 1e3, 1)}
            if tf > peak_tflops * 1.02:
                # still impossible: record the raw numbers but mark the
                # row invalid rather than publishing a >peak figure
                rec["valid"] = False
                rec["note"] = (f"{tf:.1f} TF/s exceeds nominal peak "
                               f"{peak_tflops}; marginal under-resolved")
            else:
                rec["valid"] = True
            out.append(rec)
        except Exception as e:  # OOM at the largest size is fine
            out.append({"n": n, "error": str(e)[:120]})
    # batched (closer to a transformer step's shape mix); chain via a
    # projection back to the input shape
    for (b, m, k, n) in ((8, 1024, 768, 2048), (8, 2048, 2048, 5504)):
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            (b, m, k)) * 0.01, jnp.bfloat16)
        w = jnp.asarray(np.random.default_rng(1).standard_normal(
            (k, n)) * 0.01, jnp.bfloat16)
        w2 = jnp.asarray(np.random.default_rng(2).standard_normal(
            (n, k)) * 0.01, jnp.bfloat16)
        body = lambda x, w=w, w2=w2: jnp.tanh((x @ w) @ w2)  # noqa: E731
        t5 = timed_device(body, x, iters=10) * 10
        t45 = timed_device(body, x, iters=110) * 110
        dt = (t45 - t5) / 100
        tf = 2 * b * m * k * n * 2 / dt / 1e12  # two matmuls per iter
        out.append({"shape": f"[{b},{m},{k}]x[{k},{n}] (x2, chained)",
                    "ms": round(dt * 1e3, 3), "tflops": round(tf, 1)})
    return out


def attention_paths():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels.flash_attention import flash_attention

    res = []
    b, h, d = 4, 12, 64
    for s in (1024, 4096, 8192):
        # kernel layout [b, h, s, d]; chain via the output (same shape)
        q = jnp.asarray(np.random.default_rng(0).standard_normal(
            (b, h, s, d)) * 0.1, jnp.bfloat16)

        def xla_sdpa(q, s=s):
            logits = jnp.einsum("bhqd,bhkd->bhqk", q, q)
            m = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(m, logits, -1e9).astype(jnp.float32)
            p = jax.nn.softmax(logits, -1).astype(q.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", p, q)

        def marginal(fn):
            t3 = timed_device(fn, q, iters=3) * 3
            t15 = timed_device(fn, q, iters=13) * 13
            return (t15 - t3) / 10

        row = {"seq": s}
        try:
            row["xla_ms"] = round(marginal(xla_sdpa) * 1e3, 2)
        except Exception as e:
            row["xla_error"] = str(e)[:80]
        try:
            row["pallas_ms"] = round(marginal(
                lambda q: flash_attention(q, q, q, causal=True)) * 1e3, 2)
        except Exception as e:
            row["pallas_error"] = str(e)[:80]
        res.append(row)

    # 1B-config TRAINING shapes (fwd+bwd, GQA-native k/v, b=1 s=2048):
    # the regime the llama_1b bench runs in. Chained through dq (same
    # shape as q) so the backward cannot be elided.
    for (h, hkv, d) in ((32, 4, 64), (16, 4, 128)):
        s = 2048
        q = jnp.asarray(np.random.default_rng(0).standard_normal(
            (1, h, s, d)) * 0.1, jnp.bfloat16)
        kv = jnp.asarray(np.random.default_rng(1).standard_normal(
            (1, hkv, s, d)) * 0.1, jnp.bfloat16)
        g = h // hkv

        def gqa_sdpa(q, kv=kv, g=g, s=s, d=d, hkv=hkv):
            qg = q.reshape(1, hkv, g, s, d)
            logits = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kv) / (d ** 0.5)
            m = jnp.tril(jnp.ones((s, s), bool))
            logits = jnp.where(m, logits, -1e9).astype(jnp.float32)
            p = jax.nn.softmax(logits, -1).astype(q.dtype)
            return jnp.einsum("bhgqk,bhkd->bhgqd", p, kv).reshape(q.shape)

        def fwdbwd(fn):
            return jax.grad(lambda q: jnp.sum(fn(q).astype(jnp.float32)))

        def marginal2(fn):
            t3 = timed_device(fn, q, iters=3) * 3
            t13 = timed_device(fn, q, iters=13) * 13
            return (t13 - t3) / 10

        row = {"train_shape": f"b1 h{h} hkv{hkv} s{s} d{d}"}
        try:
            row["xla_fwdbwd_ms"] = round(marginal2(fwdbwd(gqa_sdpa)) * 1e3, 2)
        except Exception as e:
            row["xla_error"] = str(e)[:80]
        for bq, bk in ((128, 128), (256, 512), (512, 512)):
            try:
                t = marginal2(fwdbwd(
                    lambda q, bq=bq, bk=bk: flash_attention(
                        q, kv, kv, causal=True, block_q=bq, block_k=bk)))
                row[f"pallas_{bq}x{bk}_fwdbwd_ms"] = round(t * 1e3, 2)
            except Exception as e:
                row[f"pallas_{bq}x{bk}_error"] = str(e)[:80]
        # jax's production splash kernel, GQA-NATIVE (the MQA entry —
        # grouped K/V, no repeat): the same wrapper
        # PADDLE_TPU_ATTN_IMPL=splash engages at the step level
        try:
            from paddle_tpu.kernels import splash_attention
            t = marginal2(fwdbwd(
                lambda q: splash_attention(q, kv, kv, causal=True)))
            row["splash_gqa_fwdbwd_ms"] = round(t * 1e3, 2)
        except Exception as e:
            row["splash_error"] = str(e)[:80]
        res.append(row)
    return res


def step_breakdown():
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, LlamaConfig
    from paddle_tpu.jit import _Installed, _collect_state
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.core import autograd as _ag

    cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                      intermediate_size=2048, num_hidden_layers=12,
                      num_attention_heads=12, num_key_value_heads=12,
                      max_position_embeddings=1024, loss_chunk_size=2048)
    batch, seq = 8, 1024
    model = LlamaForCausalLM(cfg)
    params, buffers = _collect_state(model)
    state = {**params, **buffers}
    inst = _Installed(state)

    def loss_of(state_arrays, ids):
        with inst:
            inst.install(state_arrays)
            with paddle.amp.auto_cast(enable=True, level="O1",
                                      dtype="bfloat16"):
                return model(Tensor(ids), labels=Tensor(ids))[1]._data

    def fwd(state_arrays, ids):
        with _ag.no_grad():
            return loss_of(state_arrays, ids)

    import jax.numpy as jnp
    from jax import lax
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)))
    arrs = {k: t._data for k, t in state.items()}

    def marginal(once_fn, lo=2, hi=6):
        """In-device loop, chained through the loss so iterations cannot
        overlap; marginal slope removes the fixed dispatch overhead."""
        def loop(k):
            def body(i, ids_c):
                l = once_fn(arrs, ids_c)
                return ids_c + l.astype(jnp.int32) * 0
            f = jax.jit(lambda ids0: jnp.sum(
                lax.fori_loop(0, k, body, ids0)))
            int(f(ids))  # compile + run (readback = true sync)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                int(f(ids))
                best = min(best, time.perf_counter() - t0)
            return best
        return (loop(hi) - loop(lo)) / (hi - lo)

    t_fwd = marginal(lambda a, i: fwd(a, i))

    def fwd_bwd(state_arrays, ids):
        p_keys = [k for k in state_arrays if not k.startswith("b:")]

        def pure(p_arrays):
            merged = {**state_arrays, **p_arrays}
            with _ag.no_grad():
                return loss_of(merged, ids)
        l, g = jax.value_and_grad(pure)({k: state_arrays[k] for k in p_keys})
        return l, g

    def fwd_bwd_scalar(a, i):
        l, g = fwd_bwd(a, i)
        # fold EVERY grad leaf in so no part of the backward is dead code
        tot = sum(jnp.sum(v).astype(jnp.float32) for v in g.values())
        return l + tot * 0

    t_fwd_bwd = marginal(fwd_bwd_scalar)

    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(i):
        with paddle.amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            return model(i, labels=i)[1]
    step = paddle.jit.TrainStep(model, loss_fn, opt)
    tens = Tensor(ids)
    _ = float(step(tens).numpy())
    t0 = time.perf_counter()
    for _ in range(10):
        loss = step(tens)
    float(loss.numpy())
    t_step = (time.perf_counter() - t0) / 10

    flops_tok = model.flops_per_token(seq)
    toks = batch * seq
    return {
        "config": "llama_125m b=8 s=1024 bf16-O1",
        "flops_per_token_fwd_bwd": flops_tok,
        "fwd_ms": round(t_fwd * 1e3, 2),
        "fwd_bwd_ms": round(t_fwd_bwd * 1e3, 2),
        "full_step_ms": round(t_step * 1e3, 2),
        "optimizer_overhead_ms": round((t_step - t_fwd_bwd) * 1e3, 2),
        "achieved_model_tflops": round(toks * flops_tok / t_step / 1e12, 1),
        "tokens_per_sec": round(toks / t_step, 1),
    }


def main():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()      # repeated audit runs skip recompiles
    dev = jax.devices()[0]
    out = {"device": getattr(dev, "device_kind", str(dev)),
           "platform": dev.platform}
    # dispatch+sync round-trip: the floor any per-iteration-synced
    # measurement carries
    noop = jax.jit(lambda x: x + 1)
    out["rtt_ms"] = round(timed(noop, jnp.zeros(()), iters=20) * 1e3, 3)
    print("rtt_ms:", out["rtt_ms"], flush=True)
    path = os.path.join(REPO, "docs", "PERF_AUDIT.json")
    if os.path.exists(path):  # sectioned runs merge into one artifact
        try:
            prev = json.load(open(path))
            prev.update(out)
            out = prev
        except Exception:
            pass
    sections = [s for s in sys.argv[1:] if not s.startswith("-")] \
        or ["matmul", "attention", "step"]
    if "matmul" in sections:
        print("== matmul roofline ==", flush=True)
        out["matmul_roofline"] = matmul_roofline()
        print(json.dumps(out["matmul_roofline"], indent=1), flush=True)
    if "attention" in sections:
        print("== attention paths ==", flush=True)
        out["attention"] = attention_paths()
        print(json.dumps(out["attention"], indent=1), flush=True)
    if "step" in sections:
        print("== step breakdown ==", flush=True)
        out["step"] = step_breakdown()
        print(json.dumps(out["step"], indent=1), flush=True)
    os.makedirs(os.path.join(REPO, "docs"), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote docs/PERF_AUDIT.json")


if __name__ == "__main__":
    main()
