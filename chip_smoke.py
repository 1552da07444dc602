#!/usr/bin/env python3
"""The quickest proof that paddle_tpu still starts on the chip.

One process, JAX touched only here, two legs through the entry points a
user calls, at TinyLlama-1.1B widths (vocab 32000, hidden 2048, ffn
5632, 32 q / 4 kv heads, tied embeddings) with seeded random weights:

- serve: ``paddle_tpu.serving.LLMEngine`` over all 22 layers in bf16,
  default flags, a handful of requests admitted together; greedy tokens
  against ``Generator.generate`` on the same prompts.
- latent: ``kernels/paged_attention.py::ragged_latent_attention`` alone at
  dots.vlm1.inst's head sizes (128 heads against rows of 512 + 64 values
  held in 640 lanes), a few pages, decode rows and a chunk in one launch,
  behind ``kv_append``; against its ``jnp`` reference.
- ragged: ``kernels/paged_attention.py::ragged_paged_attention`` alone at
  Qwen3-Next's full layers (2 kv heads x 8 q heads of 256: a 256-token
  chunk behind 5 k tokens, walked a q tile at a time, beside decode
  rows) and at Mistral-7B's (8 kv heads x 4 of 128: a 64-token chunk
  behind 1 k, decode rows); against its ``jnp`` reference.
- gated delta: ``kernels/gated_delta.py::ragged_gated_delta`` alone at
  Qwen3-Next's linear heads (32 value heads over 16 key heads of 128),
  decode rows and a 200-token chunk in one launch from float32 state
  slots, then again with the chunk's tokens all one key; against its
  token-by-token ``jnp`` recurrence.
- train: ``paddle.jit.TrainStep`` + AdamW + bf16 autocast + remat at
  b 1 x s 2048, as deep as one 16 GB chip holds; loss finite and falling
  on a repeated batch.

``--chips 4`` runs instead, and only, the sharded train step
(``TrainStep(sharding="dp=2,tp=2")``) and the single-device step it is
compared with. ``--rehearse`` runs whichever mode at toy size so the
control flow can be walked on a CPU; it never yields a result.

Without an accelerator this exits non-zero and prints no result line.
This is not the benchmark: no time is printed under a metric's name.
The last line on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
import argparse
import gc
import json
import re
import sys
import time

import numpy as np

WIDTHS = dict(vocab_size=32000, hidden_size=2048, intermediate_size=5632,
              num_attention_heads=32, num_key_value_heads=4,
              max_position_embeddings=2048, tie_word_embeddings=True)
TOY_WIDTHS = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                  num_attention_heads=4, num_key_value_heads=2,
                  max_position_embeddings=256, tie_word_embeddings=True)

# what each mode runs: (real, --rehearse)
SERVE = dict(layers=(22, 2), max_len=(1024, 128),
             prompt_lens=((192, 320, 192, 320), (24, 40, 24, 40)),
             new_tokens=(32, 8))
# Depth as far as 16 GB allows, decided from compiled.memory_analysis()
# of this very step for a described v5e (rehearsal 3), not by trial on
# the chip: f32 params and both AdamW moments are 12 B a parameter, the
# grads and the fused optimizer's flat param/grad staging another 16 B
# (sized before PR 40 took the staging out of the compiled step: the
# depth stands, with room to spare now).
# Of 15.75 GiB: 10 layers take 13.3 GiB at b 1 and 13.9 GiB at b 2 (the
# four-chip leg's single-device comparison); 11 take 14.5 and 15.1, 12
# take 15.6. Both train legs share the depth, so 10.
# the latent kernel: heads, row width held, value width, page slots a row
LATENT = dict(heads=(128, 4), row=(640, 128), width=(576, 40),
              v_width=(512, 32), pages=(64, 24))
# the ragged kernel: (kv heads, q heads a kv head, head size, chunk
# tokens, context before the chunk, decode rows) a geometry
RAGGED = dict(geometries=(
    (("qwen3-next", 2, 8, 256, 256, 5120, 22),
     ("mistral-7b", 8, 4, 128, 64, 1024, 24)),
    (("qwen3-next", 2, 8, 32, 136, 300, 3),
     ("mistral-7b", 2, 4, 32, 40, 100, 3))))
# the gated-delta kernel: value heads, key heads, head size, chunk tokens
GATED_DELTA = dict(heads=(32, 4), key_heads=(16, 2), dim=(128, 16),
                   chunk=(200, 20))
TRAIN = dict(layers=(10, 2), batch=(1, 1), seq=(2048, 128), steps=(4, 3))
SHARDED = dict(layers=(10, 2), batch=(2, 2), seq=(2048, 128), steps=(3, 3),
               preset="dp=2,tp=2")

# a margin, in logits, inside which two bf16 evaluations of the same
# position may order two tokens differently: seeded weights give flat
# logits (sd ~0.9 over the vocabulary), a wrong KV page or position
# moves the chosen token's logit by whole units
LOGIT_TOL = 0.125
LOSS_RTOL = 2e-2   # bf16 matmuls, sharded vs single-device reduction order


class SmokeFailure(RuntimeError):
    pass


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def pick(spec, key, rehearse):
    return spec[key][1 if rehearse else 0]


def kernels_in_hlo(text):
    """{kernel: count} of the Pallas kernels in a compiled module, by the
    stable ``name=`` each pallas_call carries into its op_name."""
    found = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*?(\w+)/pallas_call', line)
        name = m.group(1) if m else "unnamed"
        found[name] = found.get(name, 0) + 1
    return found


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _margin(logits, tokens):
    """How far each position's chosen token sits below that position's
    best logit, at worst. logits [n, vocab], tokens [n]."""
    ref = np.asarray(logits, np.float32)
    check(np.isfinite(ref).all(), "serve: non-finite reference logits")
    return float((ref.max(-1) - ref[np.arange(len(tokens)), tokens]).max())


def build_model(paddle, widths, layers, *, bf16=False, **cfg_kw):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)      # every leg's weights come from the one seed
    model = LlamaForCausalLM(LlamaConfig(num_hidden_layers=layers,
                                         **widths, **cfg_kw))
    return model.bfloat16() if bf16 else model


# ---------------------------------------------------------------------------
# serve leg
# ---------------------------------------------------------------------------

def serve_leg(paddle, dev, on_chip, rehearse):
    import jax
    from paddle_tpu.models.generation import Generator
    from paddle_tpu.serving import LLMEngine

    widths = TOY_WIDTHS if rehearse else WIDTHS
    layers = pick(SERVE, "layers", rehearse)
    max_len = pick(SERVE, "max_len", rehearse)
    new = pick(SERVE, "new_tokens", rehearse)
    model = build_model(paddle, widths, layers, bf16=True)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, widths["vocab_size"], n).tolist()
               for n in pick(SERVE, "prompt_lens", rehearse)]

    engine = LLMEngine(model, max_len=max_len, page_size=16)
    # the step's compiled text first: it tells which kernels the step
    # holds, and its compile seeds the persistent cache for the dispatch
    t0 = time.perf_counter()
    found = kernels_in_hlo(engine.ragged_step_hlo())
    compile_s = time.perf_counter() - t0
    if on_chip:
        check(found.get("ragged_paged_attention", 0) >= layers,
              f"serve: the ragged step should hold one "
              f"ragged_paged_attention tpu_custom_call per layer "
              f"({layers}), compiled text has {found}")
    ids = [engine.add_request(p, max_new_tokens=new) for p in prompts]
    outs = engine.run(max_steps=64 * len(prompts) + 8 * new)
    for rid in ids:
        o = outs[rid]
        check(o.status == "finished" and len(o.token_ids) == new,
              f"serve: request {rid} ended {o.status}/{o.finish_reason} "
              f"with {len(o.token_ids)} of {new} tokens")
    got = [list(outs[rid].token_ids) for rid in ids]
    snap = engine.metrics_snapshot()

    gen = Generator(model, max_len=max_len)
    want = []
    for p in prompts:
        full = np.asarray(gen.generate(np.asarray([p]), max_new_tokens=new)
                          .numpy())[0]
        want.append([int(t) for t in full[len(p):]])

    agree = sum(a == b for g, w in zip(got, want) for a, b in zip(g, w))
    total = new * len(prompts)
    form = "exact token identity with Generator.generate"
    if agree != total:
        # bf16 rounding between the batched ragged shapes and the
        # sequential ones may flip a near-tied argmax, after which the
        # two continuations differ for good. Hold the engine to logits
        # instead: its first decode token against Generator's logits
        # there, and every token it emitted against a teacher-forced
        # forward of the model over the engine's own sequence.
        first = max(_margin(gen._prefill(gen._prefill_params,
                                         jax.numpy.asarray([p]))[0],
                            g[:1]) for p, g in zip(prompts, got))
        width = max(len(p) for p in prompts) + new
        seqs = np.zeros((len(prompts), width), np.int64)
        for i, (p, g) in enumerate(zip(prompts, got)):
            seqs[i, :len(p) + new] = p + g     # causal: the tail pad is inert
        with paddle.no_grad():
            logits = model(paddle.to_tensor(seqs, dtype="int64"))._data
        forced = max(_margin(logits[i, len(p) - 1:len(p) - 1 + new], g)
                     for i, (p, g) in enumerate(zip(prompts, got)))
        check(max(first, forced) <= LOGIT_TOL,
              f"serve: an engine token sits below the reference's best "
              f"logit by {first:.4f} (first decode position, Generator) / "
              f"{forced:.4f} (any position, teacher-forced forward); "
              f"tolerance {LOGIT_TOL}")
        form = (f"logits: first decode position within {first:.4f} of "
                f"Generator's best, every emitted token within "
                f"{forced:.4f} of a teacher-forced forward's best "
                f"(tolerance {LOGIT_TOL}); {agree}/{total} tokens equal "
                f"Generator.generate's")
    print(json.dumps({
        "leg": "serve", "device_kind": dev.device_kind, "devices": 1,
        "layers": layers, "dtype": "bfloat16", "max_len": max_len,
        "requests": len(prompts), "prompt_lens": [len(p) for p in prompts],
        "tokens_done": sum(len(g) for g in got),
        "engine_steps": snap.get("host_dispatches"),
        "step_executables": engine.decode_cache_size(),
        "compile_seconds": round(compile_s, 1),   # the AOT lower+compile
        "kernels_in_hlo": found, "tokens_agree": f"{agree}/{total}",
        "compared": form, "peak_bytes_in_use": peak_bytes(dev)}),
        flush=True)


# ---------------------------------------------------------------------------
# train legs
# ---------------------------------------------------------------------------

def latent_leg(on_chip, rehearse):
    """The latent attention kernel behind the row append, at the
    published head sizes: a decode row deep in its context, one that
    ends mid-page, and a 40-token chunk, in one launch."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (
        kv_append, ragged_latent_attention, ragged_latent_attention_reference)
    h, row, width, v_width, pps = (pick(LATENT, k, rehearse) for k in
                                   ("heads", "row", "width", "v_width",
                                    "pages"))
    ps, qb = 16, 8
    rng = np.random.default_rng(0)
    q_lens = np.array([1, 1, 40, 0], np.int32)
    kv_lens = np.array([pps * ps - 3, 37, 40 + 2 * ps, 0], np.int32)
    slots = -(-q_lens // qb) * qb
    t = int(slots.sum()) + qb
    q_starts = np.where(q_lens > 0, np.cumsum(slots) - slots, t) \
        .astype(np.int32)
    n_pages = 1 + int((-(-kv_lens // ps)).sum())
    tbl, at = np.zeros((len(q_lens), pps), np.int32), 1
    for i, kl in enumerate(kv_lens):
        n = -(-int(kl) // ps)
        tbl[i, :n] = np.arange(at, at + n)
        at += n
    dt = jnp.bfloat16 if on_chip else jnp.float32

    def padded(a):      # the row's padding lanes are zero, as the step's
        return jnp.asarray(np.where(np.arange(row) < width, a, 0.0), dt)
    pool = padded(rng.standard_normal((n_pages, ps, row)))
    q = padded(rng.standard_normal((t, h, row)) * 0.3)
    new = padded(rng.standard_normal((t, row)))
    # this launch's tokens are appended first, as the layer body does
    pos = np.zeros((t,), np.int64)
    page = np.zeros((t,), np.int64)                  # dead: the null page
    for i, (s0, ql, kl) in enumerate(zip(q_starts, q_lens, kv_lens)):
        p = kl - ql + np.arange(ql)
        pos[s0:s0 + ql] = p
        page[s0:s0 + ql] = tbl[i, p // ps]
    slot = jnp.asarray(page * ps + pos % ps, jnp.int32)
    pool = kv_append(pool[None], slot, new[None], interpret=not on_chip)[0]
    args = (q, pool, jnp.asarray(tbl), jnp.asarray(q_starts),
            jnp.asarray(q_lens), jnp.asarray(kv_lens))
    got = ragged_latent_attention(*args, v_width=v_width, scale=0.135,
                                  q_block=qb, interpret=not on_chip)
    want = ragged_latent_attention_reference(
        q, pool, tbl, q_starts, q_lens, kv_lens, v_width=v_width,
        scale=0.135)
    live = np.zeros((t,), bool)
    for s0, ql in zip(q_starts, q_lens):
        live[s0:s0 + ql] = True
    check(bool(jnp.isfinite(got).all()), "latent: non-finite output")
    err = float(jnp.abs(got.astype(jnp.float32) - want)[live].max())
    # bf16 products with f32 accumulation against an f32 oracle on the
    # same bf16 inputs: outputs are means of N(0, 1) rows, sized ~0.1-1
    tol = 3e-2 if on_chip else 1e-4
    check(err <= tol, f"latent: kernel differs from its reference by "
                      f"{err:.4f} (limit {tol})")
    # the append wrote this launch's rows where the tables say
    landed = np.asarray(pool.astype(jnp.float32))[page[live], (pos % ps)[live]]
    check(np.array_equal(landed, np.asarray(new.astype(jnp.float32))[live]),
          "latent: appended rows are not where the tables say")
    print(json.dumps({"leg": "latent", "heads": h, "row": row,
                      "width": width, "tokens": int(q_lens.sum()),
                      "max_abs_err": err, "limit": tol}), flush=True)


def ragged_leg(on_chip, rehearse):
    """The ragged paged-attention kernel at two published geometries:
    decode rows at contexts up to the chunk's, a long chunk walked a q
    tile at a time behind its prefix, and a pad row, in one launch."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.paged_attention import (
        ragged_kv_walks, ragged_paged_attention,
        ragged_paged_attention_reference, ragged_q_tile)
    ps, qb = 16, 8
    dt = jnp.bfloat16 if on_chip else jnp.float32
    for name, hkv, group, d, chunk, prefix, decode in pick(
            RAGGED, "geometries", rehearse):
        rng = np.random.default_rng(2)
        q_lens = np.array([chunk] + [1] * decode + [0], np.int32)
        kv_lens = np.concatenate([
            [prefix + chunk], rng.integers(1, prefix + chunk, decode),
            [0]]).astype(np.int32)
        slots = -(-q_lens // qb) * qb
        t = int(slots.sum()) + qb
        q_starts = np.where(q_lens > 0, np.cumsum(slots) - slots, t) \
            .astype(np.int32)
        pps = -(-int(kv_lens.max()) // ps)
        n_pages = len(q_lens) * pps + 1
        tbl = (rng.permutation(n_pages - 1)[:len(q_lens) * pps] + 1) \
            .reshape(len(q_lens), pps).astype(np.int32)
        q = jnp.asarray(rng.standard_normal((t, hkv * group, d)), dt)
        kp, vp = (jnp.asarray(rng.standard_normal((hkv, n_pages, ps, d)), dt)
                  for _ in range(2))
        args = (q, kp, vp, jnp.asarray(tbl), jnp.asarray(q_starts),
                jnp.asarray(q_lens), jnp.asarray(kv_lens))
        got = ragged_paged_attention(*args, q_block=qb,
                                     interpret=not on_chip)
        want = ragged_paged_attention_reference(*args)
        live = np.zeros((t,), bool)
        for s0, ql in zip(q_starts, q_lens):
            live[s0:s0 + ql] = True
        check(bool(jnp.isfinite(got).all()), f"ragged ({name}): non-finite")
        err = float(jnp.abs(got.astype(jnp.float32) - want)[live].max())
        # bf16 queries and pages, f32 in the kernel and in the oracle:
        # outputs are means of N(0, 1) rows, sized ~0.01-1
        tol = 3e-2 if on_chip else 1e-4
        check(err <= tol, f"ragged ({name}): kernel differs from its "
                          f"reference by {err:.4f} (limit {tol})")
        tile = ragged_q_tile(qb, group, t)
        print(json.dumps({"leg": "ragged", "geometry": name, "kv_heads": hkv,
                          "group": group, "dim": d, "chunk": chunk,
                          "prefix": prefix, "decode_rows": decode,
                          "q_tile": tile, "walks": ragged_kv_walks(
                              q_lens, q_block=qb, q_tile=tile),
                          "max_abs_err": err, "limit": tol}), flush=True)


def gated_delta_leg(on_chip, rehearse):
    """The gated-delta recurrence over state slots at the published
    linear heads: two decode rows from their slots, a chunk that ends
    mid-block from its slot, a fresh row, in one launch."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.gated_delta import (gated_delta_reference,
                                                ragged_gated_delta)
    h, hk, d, chunk = (pick(GATED_DELTA, k, rehearse)
                       for k in ("heads", "key_heads", "dim", "chunk"))
    qb, slots = 8, 6
    rng = np.random.default_rng(1)
    q_lens = np.array([1, chunk, 1, 13, 0], np.int32)
    fresh = np.array([0, 0, 0, 1, 0], np.int32)
    rows_slot = np.array([3, 1, 5, 2, 0], np.int32)
    blocks = -(-q_lens // qb) * qb
    t = int(blocks.sum()) + qb
    q_starts = np.where(q_lens > 0, np.cumsum(blocks) - blocks, t) \
        .astype(np.int32)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((t, hk, d))) * d ** -0.5
    k = unit(rng.standard_normal((t, hk, d)))
    v = rng.standard_normal((t, h, d))
    g = -np.log1p(np.exp(rng.standard_normal((t, h)))) * rng.uniform(
        0.01, 4.0, (1, h))
    beta = 1 / (1 + np.exp(-rng.standard_normal((t, h))))
    state = (rng.standard_normal((slots, h, d, d)) * 0.1).astype(np.float32)
    # float32 on both sides; the chunked form sums in another order
    tol = 1e-3 if on_chip else 1e-4
    rep = h // hk

    def launch(case, k, g, beta):
        ops = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta, state)]
        got, new_state = ragged_gated_delta(
            *ops, jnp.asarray(rows_slot), jnp.asarray(q_starts),
            jnp.asarray(q_lens), jnp.asarray(fresh), q_block=qb,
            interpret=not on_chip)
        err = 0.0
        for r in np.flatnonzero(q_lens):
            s0, n = int(q_starts[r]), int(q_lens[r])
            s_in = np.zeros((h, d, d)) if fresh[r] else state[rows_slot[r]]
            want_o, want_s = gated_delta_reference(
                *(jnp.asarray(x, jnp.float32) for x in (
                    np.repeat(q[s0:s0 + n], rep, 1),
                    np.repeat(k[s0:s0 + n], rep, 1),
                    v[s0:s0 + n], g[s0:s0 + n], beta[s0:s0 + n], s_in)))
            err = max(err, float(jnp.abs(got[s0:s0 + n] - want_o).max()),
                      float(jnp.abs(new_state[rows_slot[r]] - want_s).max()))
        check(bool(jnp.isfinite(got[:int(blocks.sum())]).all()),
              f"gated delta ({case}): non-finite output")
        check(err <= tol, f"gated delta ({case}): kernel differs from its "
                          f"recurrence by {err:.2e} (limit {tol})")
        untouched = np.asarray(new_state[np.array([0, 4])]) \
            - state[np.array([0, 4])]
        check(not untouched.any(),
              f"gated delta ({case}): a slot no row holds moved")
        print(json.dumps({"leg": "gated_delta", "case": case, "heads": h,
                          "key_heads": hk, "dim": d,
                          "tokens": int(q_lens.sum()), "max_abs_err": err,
                          "limit": tol}), flush=True)

    launch("random keys", k, g, beta)
    # the chunk row's tokens all one key (a repeated token), written
    # strongly and hardly forgotten: the case an unstable inverse of the
    # chunk's triangular system gets wrong by orders of magnitude
    s0, n = int(q_starts[1]), int(q_lens[1])
    k, g, beta = k.copy(), g.copy(), beta.copy()
    k[s0:s0 + n] = k[s0]
    g[s0:s0 + n], beta[s0:s0 + n] = -1e-3, 0.9
    launch("one key repeated", k, g, beta)


def train_steps(paddle, widths, layers, batch, seq, steps, sharding=None):
    """Build model + AdamW + TrainStep from the seed, take ``steps`` steps
    on one repeated batch. Returns (losses, step, model, seconds the
    first call took: compile and step)."""
    model = build_model(paddle, widths, layers, loss_chunk_size=512,
                        remat=True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(ids):
        with paddle.amp.auto_cast(enable=True, level="O1",
                                  dtype="bfloat16"):
            return model(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt, sharding=sharding,
                                capture_hlo=True)
    ids = paddle.to_tensor(
        np.random.default_rng(1).integers(0, widths["vocab_size"],
                                          (batch, seq)), dtype="int64")
    t0 = time.perf_counter()
    losses = [float(step(ids).numpy())]
    first_call_s = time.perf_counter() - t0
    losses += [float(step(ids).numpy()) for _ in range(steps - 1)]
    check(all(np.isfinite(l) for l in losses),
          f"train: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on a repeated batch: {losses}")
    return losses, step, model, first_call_s


def expect_train_kernels(found, seq, on_chip, leg):
    """What the code selects on a TPU at these shapes, named beforehand:
    no fused_adamw (a compiled step updates every leaf where it lies;
    the Pallas bucket kernel is the eager ``opt.step()``'s),
    flash_attention only from the threshold up."""
    from paddle_tpu.kernels import flash_threshold
    flash = seq >= flash_threshold() and seq % 128 == 0
    check(found.get("fused_adamw", 0) == 0,
          f"{leg}: a compiled step builds no flat bucket and calls no "
          f"fused_adamw kernel, compiled text has {found}")
    if on_chip:
        check((found.get("flash_attention_fwd", 0) >= 1) == flash,
              f"{leg}: flash_attention expected={flash} at s={seq} "
              f"(threshold {flash_threshold()}), compiled text has {found}")
    return ("pallas flash_attention" if found.get("flash_attention_fwd")
            else f"XLA composition (flash from s {flash_threshold()})")


def train_leg(paddle, dev, on_chip, rehearse):
    widths = TOY_WIDTHS if rehearse else WIDTHS
    layers, batch, seq, steps = (pick(TRAIN, k, rehearse) for k in
                                 ("layers", "batch", "seq", "steps"))
    held_before = (dev.memory_stats() or {}).get("bytes_in_use")
    losses, step, model, first_call_s = train_steps(
        paddle, widths, layers, batch, seq, steps)
    found = kernels_in_hlo(step.last_hlo_text or "")
    attention = expect_train_kernels(found, seq, on_chip, "train")
    print(json.dumps({
        "leg": "train", "device_kind": dev.device_kind, "devices": 1,
        "layers": layers, "layers_of": 22, "batch": batch, "seq": seq,
        "params": sum(p.size for p in model.parameters()),
        "steps_done": len(losses), "losses": [round(l, 4) for l in losses],
        "first_call_seconds": round(first_call_s, 1),
        "attention_body": attention, "kernels_in_hlo": found,
        "bytes_in_use_before_leg": held_before,
        "peak_bytes_in_use": peak_bytes(dev)}), flush=True)


def sharded_leg(paddle, devs, on_chip, rehearse):
    """TrainStep(sharding="dp=2,tp=2") on the first four devices against
    the single-device step: same seed, same batch."""
    widths = TOY_WIDTHS if rehearse else WIDTHS
    layers, batch, seq, steps = (pick(SHARDED, k, rehearse) for k in
                                 ("layers", "batch", "seq", "steps"))
    single, step, model, _ = train_steps(paddle, widths, layers, batch,
                                         seq, steps)
    found_single = kernels_in_hlo(step.last_hlo_text or "")
    del step, model
    gc.collect()

    losses, step, model, _ = train_steps(paddle, widths, layers, batch,
                                         seq, steps,
                                         sharding=SHARDED["preset"])
    found = kernels_in_hlo(step.last_hlo_text or "")
    expect_train_kernels(found, seq, on_chip, "sharded")
    for a, b in zip(single, losses):
        check(abs(a - b) <= LOSS_RTOL * abs(a),
              f"sharded: losses {losses} leave the single-device step's "
              f"{single} by more than {LOSS_RTOL:.0%}")
    # no device holds the whole parameter set: tp=2 halves every
    # projection and the embedding, norms replicate
    total = sum(p._data.nbytes for p in model.parameters())
    per_dev = {}
    for p in model.parameters():
        for sh in p._data.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    check(len(per_dev) == 4, f"sharded: params sit on {sorted(per_dev)}")
    check(max(per_dev.values()) <= 0.55 * total,
          f"sharded: a device holds {max(per_dev.values())} of {total} "
          f"parameter bytes — not split over tp=2")
    # tp and dp reduce; the optimizer updates the shards each chip holds,
    # so nothing is gathered for it (the tied head's loss may gather)
    from paddle_tpu.jit.hlo_forensics import instruction_metadata
    from paddle_tpu.profiler import phases
    coll = step.last_hlo_collectives or {}
    table = phases.parse(step.last_hlo_text or "")[0]
    gathered = [n for n, op, _, _ in
                instruction_metadata(step.last_hlo_text or "")
                if op.startswith("all-gather")
                and table[n][0] == "optimizer"]
    check(coll.get("all_reduce", 0) >= 1 and not gathered,
          f"sharded: expected all-reduces and no all-gather under "
          f"phase.optimizer in the compiled text, found {coll}, "
          f"gathered for the optimizer: {gathered}")
    print(json.dumps({
        "leg": "sharded_train", "device_kind": devs[0].device_kind,
        "devices": 4, "preset": SHARDED["preset"], "layers": layers,
        "layers_of": 22, "batch": batch, "seq": seq,
        "losses_single_device": [round(l, 4) for l in single],
        "losses_sharded": [round(l, 4) for l in losses],
        "loss_rtol": LOSS_RTOL, "param_bytes_total": total,
        "param_bytes_per_device": per_dev, "collectives": coll,
        "kernels_in_hlo": found, "kernels_in_hlo_single": found_single,
        "peak_bytes_in_use": {d.id: peak_bytes(d) for d in devs[:4]}}),
        flush=True)


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the dp=2,tp=2 train step and the "
                         "single-device step it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes, any backend; never yields a result")
    args = ap.parse_args(argv)

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core import native
    from paddle_tpu.core.compile_cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    on_chip = dev.platform == "tpu"
    # the cache lets each step's AOT compile (for its text) and its first
    # dispatch share one compilation; a CPU rehearsal has no use for it
    cache_dir = enable_compile_cache() if on_chip else None
    print(json.dumps({
        "start": "chip_smoke", "platform": dev.platform,
        "device_kind": dev.device_kind, "devices_visible": len(devs),
        "chips": args.chips, "rehearse": args.rehearse,
        "jax": jax.__version__, "compile_cache": cache_dir,
        "native_library_loaded": bool(native.ensure_loaded())}), flush=True)
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: no accelerator (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devs)}", file=sys.stderr)
        return 2

    if args.chips == 4:
        sharded_leg(paddle, devs, on_chip, args.rehearse)
    else:
        serve_leg(paddle, dev, on_chip, args.rehearse)
        gc.collect()
        latent_leg(on_chip, args.rehearse)
        ragged_leg(on_chip, args.rehearse)
        gated_delta_leg(on_chip, args.rehearse)
        train_leg(paddle, dev, on_chip, args.rehearse)

    if not on_chip or args.rehearse:
        print("chip_smoke: rehearsal finished; no accelerator run, "
              "no result", file=sys.stderr)
        return 2
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
