"""Training cell: ``paddle.jit.TrainStep`` steps back to back for the
window, a fresh seeded batch each step. Build, recipe and warm-up follow
``chip_smoke.py::train_steps`` (copied here: the yardstick may not move
with that script)."""
import math
import time

import numpy as np

from .. import build, latency, traffic
from ..tracing import WindowTrace, span

clock = time.perf_counter


def run(ctx):
    import paddle_tpu as paddle
    cfg, mix = ctx.config, ctx.traffic
    recipe = cfg["train"]
    batch, seq = int(mix["batch"]), int(mix["seq"])
    vocab = cfg["vocab_size"]
    reference = ctx.reference(cfg)

    t_build = clock()
    model = build.build_model(paddle, cfg, ctx.seed,
                              loss_chunk_size=recipe["loss_chunk_size"],
                              remat=recipe["remat"])
    opt = paddle.optimizer.AdamW(learning_rate=recipe["lr"],
                                 parameters=model.parameters())
    amp = recipe["autocast"]

    def loss_fn(ids):
        with paddle.amp.auto_cast(enable=bool(amp), level=amp or "O1",
                                  dtype="bfloat16"):
            return model(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt,
                                sharding=mix.get("sharding"))
    n_params = sum(p.size for p in model.parameters())

    def feed(i):
        return paddle.to_tensor(
            traffic.train_batch(vocab, ctx.seed, i, batch, seq),
            dtype="int64")

    # correctness, outside the window: the reference's loss on batch 0
    # with the initial weights (before the first step donates them),
    # then three steps on that batch
    t_ref = clock()
    first = traffic.train_batch(vocab, ctx.seed, 0, batch, seq)
    want = reference.loss(reference.weights(model), cfg, first.tolist())
    ids0 = feed(0)
    t0 = clock()
    warm = [float(step(ids0).numpy())]
    first_call_s = clock() - t0
    warm += [float(step(ids0).numpy()) for _ in range(2)]
    rel = abs(warm[0] - want) / abs(want)
    correct = (all(math.isfinite(x) for x in warm)
               and rel <= recipe["loss_rtol"]
               and warm[-1] < warm[0])
    # two fed steps, so the feed path is warm as well
    nxt = feed(1)
    for i in (2, 3):
        pending = step(nxt)
        nxt = feed(i)
        float(pending.numpy())
    ctx.info(phase="warm", build_s=t_ref - t_build, reference_s=t0 - t_ref,
             first_call_s=first_call_s, warm_losses=warm,
             reference_loss=want, loss_rel_err=rel,
             loss_rtol=recipe["loss_rtol"], params=n_params)

    tracer = WindowTrace(ctx.trace, mix["trace_after_s"], mix["trace_s"],
                         ctx.keep_trace)
    t_open = clock()
    t_close = t_open + ctx.seconds
    spans, losses, i = [], [], 4
    while clock() < t_close:
        tracer.poll(clock() - t_open)
        ts = clock()
        with span("bench.step"):
            pending = step(nxt)
        with span("bench.feed_batch"):
            nxt = feed(i)
            i += 1
        with span("bench.read_loss"):
            losses.append(float(pending.numpy()))
        spans.append((ts, clock()))
    # the profiler starts and stops between two steps, with the device
    # idle: a traced run's rate leaves that standstill out (0 untraced)
    elapsed = spans[-1][1] - spans[0][0] - tracer.overhead_s
    events = tracer.events()

    steps = len(spans)
    finite = sum(1 for x in losses if math.isfinite(x))
    tok_s = steps * batch * seq / elapsed
    ctx.info(phase="window", steps=steps, tokens=steps * batch * seq,
             elapsed_s=elapsed, nonfinite_losses=steps - finite,
             loss_first=losses[0], loss_last=losses[-1],
             **latency.step_stats([b - a for a, b in spans]))
    return {
        "correct": bool(correct and finite == steps),
        "compared": {
            "loss_rel_err": [rel, recipe["loss_rtol"]],
            "loss_third_less_first": [warm[-1] - warm[0], 0.0],
            "nonfinite_losses": [steps - finite, 0]},
        "attempted": steps, "failed": steps - finite,
        "t_open": t_open,
        "end_to_end": {"train_tok_s": tok_s},
        "run": {
            "step_s": [b - a for a, b in spans],
            "n_params": n_params, "seq": seq, "batch": batch,
            "steps": steps, "events": events,
        },
    }
