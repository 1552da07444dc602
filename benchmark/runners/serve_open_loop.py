"""Serving cell: ``LLMEngine`` under open-loop arrivals on the wall
clock. One thread admits every request that is due, calls
``engine.step()``, and sleeps only when nothing runs and nothing is due.
Tokens are stamped by the benchmark in ``stream_cb``; a request's clock
starts when it was DUE, so a stall's cost to later requests counts.

Build and logit-margin check follow ``chip_smoke.py::serve_leg`` (copied
here: the yardstick may not move with that script)."""
import time

import numpy as np

from .. import build, latency, traffic
from ..tracing import WindowTrace, span

clock = time.perf_counter
COUNTERS = ("tokens_generated", "host_dispatches", "prefill_chunks",
            "decode_compiles", "preemptions", "finished_requests",
            "shed_requests", "rejected_requests")


class Served:
    """The engine, built once, and the stamps of the requests sent."""

    def __init__(self, ctx):
        import paddle_tpu as paddle
        from paddle_tpu.serving import LLMEngine
        cfg = ctx.config
        self.ctx, self.cfg = ctx, cfg
        t0 = clock()
        model = build.build_model(paddle, cfg, ctx.seed)
        if cfg["dtype"] == "bfloat16":
            model = model.bfloat16()
        elif cfg["dtype"] != "float32":
            raise ValueError(f"dtype {cfg['dtype']!r}")
        model.eval()
        list(model.parameters())[-1]._data.block_until_ready()
        self.build_s = clock() - t0
        self.model = model
        self.stamps, self.max_new = {}, {}
        # every engine argument the file does not state stays at the
        # program's default: defaults are part of what is measured
        self.engine = LLMEngine(model, stream_cb=self._on_token,
                                **cfg["engine"])
        engine_s = clock() - t0 - self.build_s
        # the one ragged executable compiles (or loads) on this request
        rid = self.engine.add_request(
            np.random.default_rng(0).integers(
                0, cfg["vocab_size"],
                ctx.traffic["prompt_len"]["min"]).tolist(),
            max_new_tokens=2)
        t0 = clock()
        self.engine.run(max_steps=64)
        self.first_steps_s = clock() - t0
        self.engine.release(rid)
        ctx.info(phase="setup", build_s=self.build_s, engine_s=engine_s,
                 first_steps_s=self.first_steps_s)

    def _on_token(self, rid, token, finished):
        ts = self.stamps.get(rid)
        if ts is None:
            return          # the warm-up request, or one of another run
        if token is not None and len(ts) < self.max_new[rid]:
            ts.append(clock())

    def counters(self):
        snap = self.engine.metrics_snapshot()
        return {k: snap.get(k, 0) for k in COUNTERS}

    def drain(self):
        """Cancel whatever still runs (between the runs of a sweep)."""
        for rid, out in self.engine.outputs().items():
            if not out.finished:
                self.engine.cancel(rid)
        while self.engine.has_unfinished():
            self.engine.step()
        for rid, out in self.engine.outputs().items():
            self.engine.release(rid)
        self.stamps, self.max_new = {}, {}

    def measure(self, seed, seconds, rate=None, trace=False):
        """Warm phase, then the window. Returns the raw record."""
        ctx, eng, mix = self.ctx, self.engine, self.ctx.traffic
        reqs = traffic.schedule(mix, self.cfg["vocab_size"], seed, seconds,
                                rate)
        warm_s = float(mix["warm_s"])
        tracer = WindowTrace(trace, mix["trace_after_s"], mix["trace_s"],
                             ctx.keep_trace)
        due, rid_of, late, steps, depth = {}, {}, [], [], []
        rejected = 0
        t0 = clock()
        t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
        before = None
        i, n = 0, len(reqs)
        while True:
            now = clock()
            if before is None and now >= t_open:
                before = self.counters()
            if now >= t_close:
                break
            if now >= t_open:
                tracer.poll(now - t_open)
            with span("bench.admit"):
                while i < n and t0 + reqs[i].due <= now:
                    r = reqs[i]
                    rid = f"s{seed}-{i}"
                    self.stamps[rid] = []
                    self.max_new[rid] = r.max_new_tokens
                    due[rid] = t0 + r.due
                    rid_of[i] = rid
                    try:
                        eng.add_request(r.prompt, request_id=rid,
                                        max_new_tokens=r.max_new_tokens)
                    except ValueError:       # RequestRejected
                        rejected += 1
                    late.append(clock() - due[rid])
                    i += 1
            if eng.has_unfinished():
                with span("bench.step"):
                    ts = clock()
                    eng.step()
                    te = clock()
                steps.append((ts, te))
                depth.append((te, len(eng.scheduler.waiting),
                              len(eng.scheduler.running)))
            else:
                with span("bench.wait_arrival"):
                    nxt = t0 + reqs[i].due if i < n else t_close
                    time.sleep(max(0.0, min(nxt, t_close) - clock()))
        events = tracer.events()
        after = self.counters()
        if before is None:
            before = after
        outs = eng.outputs()
        in_win = [rid for rid, d in due.items() if t_open <= d < t_close]
        bad = [rid for rid in in_win
               if outs[rid].status in ("shed", "aborted", "cancelled")]
        return {
            "seed": seed, "t_open": t_open, "t_close": t_close,
            "due": due, "stamps": self.stamps, "late": late,
            "steps": steps, "depth": depth, "events": events,
            "counters": {k: after[k] - before[k] for k in after},
            "in_window": in_win, "bad": bad, "rejected": rejected,
            "reqs": reqs, "rid_of": rid_of, "outs": outs,
        }

    def check(self, raw, reference, sample=4):
        """Every token the engine emitted for a seeded sample of finished
        requests sits within ``logit_tol`` of the reference's best logit
        at its position, over prompt + emitted tokens. Tokens themselves
        are not compared: in bf16 a near-tied argmax flips on rounding
        and the continuations then differ for good (PR 22)."""
        mix, tol = self.ctx.traffic, self.cfg["logit_tol"]
        done = [(i, rid) for i, rid in sorted(raw["rid_of"].items())
                if raw["outs"][rid].status == "finished"]
        if not done:
            return False, {"checked": 0}
        rng = np.random.default_rng([int(raw["seed"]), 13])
        picks = rng.choice(len(done), min(sample, len(done)), replace=False)
        w = reference.weights(self.model)
        # one shape for every sequence checked, so one compile
        top = mix["prompt_len"]["max"] + mix["answer_len"]["max"]
        width = -(-top // 128) * 128
        n_rows = mix["answer_len"]["max"]
        margins = []
        for p in sorted(picks):
            i, rid = done[p]
            prompt = raw["reqs"][i].prompt
            got = list(raw["outs"][rid].token_ids)
            seq = prompt + got
            toks = seq + [0] * (width - len(seq))    # causal: pad is inert
            rows = [len(prompt) - 1 + j for j in range(len(got))]
            rows += [0] * (n_rows - len(rows))
            lg = reference.logits_at(w, self.cfg, toks, rows)
            margins += reference.margins(lg[:len(got)], got)
        mean, worst = sum(margins) / len(margins), max(margins)
        return (mean <= tol["mean"] and worst <= tol["max"]), {
            "checked": len(picks), "positions": len(margins),
            "margin_mean": mean, "margin_max": worst,
            "margin_p95": latency.percentile(margins, 95),
            "argmax_share": sum(m == 0.0 for m in margins) / len(margins),
            "logit_tol": tol}


def run(ctx):
    served = Served(ctx)
    compiles = served.engine.decode_cache_size()
    raw = served.measure(ctx.seed, ctx.seconds, ctx.rate, ctx.trace)
    compiled_in_window = served.engine.decode_cache_size() - compiles \
        + raw["counters"]["decode_compiles"]
    ok, detail = served.check(raw, ctx.reference(ctx.config))
    summary = summarize(raw)
    ctx.info(phase="window", compiled_in_window=compiled_in_window, **summary["counts"],
             **detail)
    tol = ctx.config["logit_tol"]
    return {
        "correct": bool(ok and compiled_in_window == 0),
        "compared": {
            "margin_mean": [detail.get("margin_mean"), tol["mean"]],
            "margin_max": [detail.get("margin_max"), tol["max"]],
            "compiled_in_window": [compiled_in_window, 0]},
        "attempted": len(raw["in_window"]),
        "failed": len(raw["bad"]) + raw["rejected"],
        "t_open": raw["t_open"],
        "end_to_end": summary["end_to_end"],
        "run": {
            "step_s": [b - a for a, b in raw["steps"]
                       if raw["t_open"] <= a < raw["t_close"]],
            "counters": raw["counters"], "events": raw["events"],
        },
    }


def summarize(raw):
    """End-to-end metrics and the counts they stand on."""
    t_open, t_close = raw["t_open"], raw["t_close"]
    ttft, censored = latency.ttft_samples(raw["due"], raw["stamps"],
                                          t_open, t_close)
    gaps = latency.gap_samples(raw["stamps"], t_open, t_close)
    toks = latency.tokens_in(raw["stamps"], t_open, t_close)
    e2e = {"serve_tok_s": toks / (t_close - t_open)}
    if ttft:
        e2e["ttft_p95_ms"] = 1e3 * latency.percentile(ttft, 95)
    if gaps:
        e2e["itl_p95_ms"] = 1e3 * latency.percentile(gaps, 95)
    mid = t_open + (t_close - t_open) / 2

    def at(t, both=False):
        return next((w + (r if both else 0)
                     for ts, w, r in reversed(raw["depth"]) if ts <= t), 0)
    counts = {
        "requests_due": len(ttft), "requests_censored": censored,
        "requests_failed": len(raw["bad"]) + raw["rejected"],
        "requests_finished": raw["counters"]["finished_requests"],
        "tokens": toks, "gaps": len(gaps),
        "engine_steps": sum(1 for a, _ in raw["steps"]
                            if t_open <= a < t_close),
        "ttft_p50_ms": 1e3 * latency.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * latency.percentile(gaps, 50) if gaps else None,
        "generator_late_p95_ms":
            1e3 * latency.percentile(raw["late"], 95) if raw["late"] else None,
        "waiting_mid": at(mid), "waiting_end": at(t_close),
        "in_system_mid": at(mid, True), "in_system_end": at(t_close, True),
        "running_end": raw["depth"][-1][2] if raw["depth"] else 0,
        **latency.step_stats([b - a for a, b in raw["steps"]
                              if t_open <= a < t_close]),
    }
    return {"end_to_end": e2e, "counts": counts}
