"""Flagship benchmark: Llama decoder pretraining step throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Metric: tokens/sec through the fused compiled train step (forward + backward
+ AdamW) on a GPT2-small-scale Llama config, bf16 autocast on TPU.
``vs_baseline`` is measured MFU relative to the 45% MFU north-star target
(BASELINE.md) — >1.0 beats it. The reference publishes no in-repo numbers
(BASELINE.md), so the MFU target is the comparison axis.

The measurement runs in a child process under a wall-clock timeout — a
backend that hangs while it comes up does so inside native code where no
Python signal handler can fire, so only a process boundary bounds it.
Failures are retried once.

- The child appends staged heartbeats ("backend_up" / "compiled" / "rep k")
  to a progress file; on failure the parent embeds them in the artifact so a
  backend that never came up (no backend_up) is distinguishable from a
  compile blowup (backend_up but no compiled) without reproducing the run.
- The child uses the persistent XLA compilation cache
  (paddle_tpu/core/compile_cache.py), so a retry after a slow first compile
  starts warm instead of cold.
- The retry budget covers cold-compile (60-120 s, docs/PERF.md §5) plus the
  measurement: 600 s first try, 300 s warm retry.
- On total failure the artifact embeds the last recorded good round's number
  with an explicit ``stale: true`` marker, and the script exits non-zero:
  a replayed number is not a measurement.

ONE PROCESS PER CHIP: the children need the chip, so this parent must never
touch JAX — a parent that has initialized a backend holds the chip and its
child then fails or hangs. It stays off JAX today only because nothing it
imports at module scope does (tools.bench_probes defers its jax imports
into the probe bodies, which run in the child). Keep it so; the structure
itself is ROADMAP A1's to replace.
"""
import glob
import json
import os
import re
import statistics
import subprocess
import sys
import time

import numpy as np

# the CPU-tier probes are shared with tools/proxy_bench.py (standalone
# baseline-compare harness); bench.py keeps its artifact schema and
# spreads the same fields into the flagship JSON line
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from tools.bench_probes import (probe_disagg,  # noqa: E402
                                probe_gspmd,
                                probe_hlo_fusion,
                                probe_input_pipeline,
                                probe_kv_tiering,
                                probe_megakernel,
                                probe_multitenant,
                                probe_opt_dispatches,
                                probe_persistence, probe_pipeline,
                                probe_serving,
                                probe_spec_decode, probe_telemetry,
                                probe_tracing)

# legacy aliases: forensics tests and older tooling call the underscored
# names on this module
_probe_opt_dispatches = probe_opt_dispatches
_probe_serving = probe_serving
_probe_input_pipeline = probe_input_pipeline
_probe_spec_decode = probe_spec_decode
_probe_gspmd = probe_gspmd
_probe_hlo_fusion = probe_hlo_fusion
_probe_tracing = probe_tracing
_probe_telemetry = probe_telemetry
_probe_persistence = probe_persistence
_probe_kv_tiering = probe_kv_tiering
_probe_disagg = probe_disagg
_probe_multitenant = probe_multitenant
_probe_megakernel = probe_megakernel
_probe_pipeline = probe_pipeline

PEAK_FLOPS = {
    "tpu v5 lite": 197e12,  # v5e bf16
    "tpu v5p": 459e12,
    "tpu v5": 197e12,
    "tpu v4": 275e12,
    "tpu v6": 918e12,
}

_PROGRESS_ENV = "PADDLE_TPU_BENCH_PROGRESS"
_SENTINEL = "BENCH_RESULT_JSON:"


def peak_flops(dev) -> float:
    """Peak bf16 FLOP/s of ``dev``. A device that is not in the table is
    an error, not a default: a utilization against a made-up peak is not
    a measurement."""
    kind = str(getattr(dev, "device_kind", dev)).lower()
    for k, v in PEAK_FLOPS.items():
        if k in kind:
            return v
    raise ValueError(
        f"bench.py: no peak FLOP/s on record for device kind {kind!r} "
        f"(known: {sorted(PEAK_FLOPS)}); refusing to compute an MFU")


class _Progress:
    """Append-only staged heartbeat written by the child, read by the parent.

    Survives the child being SIGKILLed on timeout (every write is flushed),
    which is the whole point: the artifact tail must show how far the child
    got even when it never printed its result line.
    """

    def __init__(self):
        path = os.environ.get(_PROGRESS_ENV)
        self._f = open(path, "a", buffering=1) if path else None
        self._t0 = time.perf_counter()

    def mark(self, stage, **extra):
        rec = {"stage": stage, "t": round(time.perf_counter() - self._t0, 1)}
        rec.update(extra)
        if self._f:
            self._f.write(json.dumps(rec) + "\n")
            self._f.flush()


def run_bench(config="llama_125m", progress=None):
    progress = progress or _Progress()
    import jax

    # Persistent compilation cache: a retry after a slow cold compile (or a
    # later invocation) starts warm. Placed by JAX_COMPILATION_CACHE_DIR,
    # else the one fixed directory of paddle_tpu/core/compile_cache.py.
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, LlamaConfig
    progress.mark("imports_done")

    # Marked BEFORE the first backend touch: a timeout whose last stage is
    # "backend_probing" conclusively names backend init as the stall,
    # instead of leaving it inferred from "imports_done".
    progress.mark("backend_probing")
    if os.environ.get("PADDLE_TPU_BENCH_SIMULATE_HANG") == "backend":
        # forensics self-test hook: emulate a backend that never comes up
        # (jax.devices() blocking in native code) so the harness can
        # assert the artifact names backend_probing as the stalled stage
        while True:
            time.sleep(3600)
    dev = jax.devices()[0]
    peak = peak_flops(dev)      # an unknown device fails here, not after
    on_tpu = dev.platform not in ("cpu", "gpu")
    progress.mark("backend_up", device=getattr(dev, "device_kind", str(dev)))
    if config == "llama_1b" and on_tpu:
        # ~1B-param config (TinyLlama-1.1B shape) with remat + bf16: the
        # arithmetic-intensity regime of the 13B north star, sized to one
        # v5e chip (fp32 AdamW states ~13 GB; activations remat'd).
        # Flash attention is mandatory here, not a perf choice: the fp32
        # AdamW states leave ~3.5 GB of HBM for program temps, and the
        # naive composition's [b*h, s, s] scores alone need 7-14 GB
        # (measured OOM: 26.5G required vs 15.75G). Engage the Pallas
        # kernel at this seq len unless the caller already tuned it.
        os.environ.setdefault("PADDLE_TPU_FLASH_THRESHOLD", "2048")
        # tie_word_embeddings: still ~1.03B params (968M decoder + 66M
        # embedding) and saves 750 MB of fp32 head param + AdamW moments —
        # the margin that fits the step on one 16G chip.
        # PADDLE_TPU_BENCH_1B_HEADS: head-count A/B (32 -> d=64, the
        # TinyLlama geometry; 16 -> d=128, the TPU-native geometry that
        # fills the MXU's 128 contraction lanes — docs/PERF.md 2a).
        heads = int(os.environ.get("PADDLE_TPU_BENCH_1B_HEADS", 32))
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5632, num_hidden_layers=22,
                          num_attention_heads=heads, num_key_value_heads=4,
                          max_position_embeddings=2048,
                          tie_word_embeddings=True,
                          loss_chunk_size=512, remat=True)
        batch, seq, iters, reps = 1, 2048, 4, 2
    elif config == "llama_1b":
        # CPU CI stand-in: same code path (remat + chunked CE), tiny shape
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=3,
                          num_attention_heads=4, num_key_value_heads=2,
                          loss_chunk_size=128, remat=True)
        batch, seq, iters, reps = 1, 128, 2, 1
    elif on_tpu:
        # Profiled breakdown (round 2, xplane on a v5e chip): the step is
        # near this part's practical ceiling — a pure 4096^3 bf16 matmul
        # measures ~46 TF/s (23% of the 197 TF/s nominal peak used as the
        # MFU denominator), while this step sustains ~62 TF/s of model
        # FLOPs. Tried and measured end-to-end: AMP O2 (+-0%), batch 16
        # (+1%), chunked fused CE head (loss-exact, +-0%, kept for the
        # memory headroom), Pallas/splash flash attention (2.3x SLOWER than
        # the XLA composition at s<=4096 here — threshold raised to 8192).
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          intermediate_size=2048, num_hidden_layers=12,
                          num_attention_heads=12, num_key_value_heads=12,
                          max_position_embeddings=1024, loss_chunk_size=2048)
        batch, seq, iters, reps = 8, 1024, 10, 3
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=4)
        batch, seq, iters, reps = 4, 128, 5, 2

    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    # perf-path knobs recorded in the artifact: scan-over-layers + remat
    # policy come from FLAGS (env-settable), micro-batch accumulation
    # from PADDLE_TPU_BENCH_ACCUM (batch must divide by it).
    from paddle_tpu.core.flags import GLOBAL_FLAGS
    from paddle_tpu.nn.scan_stack import effective_remat_policy
    accumulate_steps = max(int(os.environ.get("PADDLE_TPU_BENCH_ACCUM",
                                              "1") or 1), 1)
    remat_policy = effective_remat_policy(cfg.remat)
    opt_probe = _probe_opt_dispatches(paddle)
    serving_probe = _probe_serving(paddle)
    spec_probe = _probe_spec_decode(paddle)
    input_pipeline_probe = _probe_input_pipeline(paddle)
    gspmd_probe = _probe_gspmd(paddle)
    pipeline_probe = _probe_pipeline(paddle)
    fusion_probe = _probe_hlo_fusion(paddle)
    tracing_probe = _probe_tracing(paddle)
    telemetry_probe = _probe_telemetry(paddle)
    persistence_probe = _probe_persistence(paddle)
    kv_tier_probe = _probe_kv_tiering(paddle)
    disagg_probe = _probe_disagg(paddle)
    multitenant_probe = _probe_multitenant(paddle)
    megakernel_probe = _probe_megakernel(paddle)
    progress.mark("model_built", config=config, **opt_probe)

    def loss_fn(ids):
        # bf16 autocast on the MXU-bound ops; fp32 master weights live in
        # the optimizer. On CPU CI keep fp32 (parity with tests).
        with paddle.amp.auto_cast(enable=on_tpu, level="O1", dtype="bfloat16"):
            return model(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt,
                                accumulate_steps=accumulate_steps)
    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)),
        dtype="int64")

    # warmup: compile + 2 steady-state steps
    _ = float(step(ids).numpy())
    progress.mark("compiled", compile_ms=round(step.last_compile_ms or 0, 1))
    _ = float(step(ids).numpy())
    progress.mark("warm")

    # reps x iters: async enqueue inside a rep, sync at rep boundary —
    # keeps the pipeline full while giving a variance estimate
    rep_dts = []
    for r in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(ids)
        val = float(loss.numpy())  # sync
        rep_dts.append(time.perf_counter() - t0)
        progress.mark(f"rep_{r + 1}", dt=round(rep_dts[-1], 3))
    if not np.isfinite(val):
        raise RuntimeError(f"non-finite loss {val}")

    tokens_per_step = batch * seq
    best = min(rep_dts)
    tok_s = tokens_per_step * iters / best
    # MFU counts the FLOPs the hardware actually executes: under
    # remat_policy=full that includes the recomputed forward.
    flops_tok = model.flops_per_token(seq, remat_policy=remat_policy)
    mfu = tok_s * flops_tok / peak
    progress.mark("measured", tok_s=round(tok_s, 1))
    return {
        "metric": f"{config}_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "device": getattr(dev, "device_kind", str(dev)),
        "batch": batch, "seq": seq,
        "step_ms": round(best / iters * 1e3, 2),
        "step_ms_stdev": round(
            (statistics.stdev(rep_dts) / iters * 1e3) if len(rep_dts) > 1
            else 0.0, 2),
        "loss": round(val, 4),
        # perf-path forensics (round-6): a trajectory jump in compile_ms
        # flags recompilation churn; peak_hbm_bytes regression-proofs the
        # remat/accumulation memory win (null when the runtime exposes no
        # memory stats — never fabricated).
        "compile_ms": round(step.last_compile_ms, 1)
        if step.last_compile_ms is not None else None,
        "peak_hbm_bytes": _peak_hbm_bytes(dev),
        "remat_policy": remat_policy,
        "accumulate_steps": accumulate_steps,
        "scan_layers": bool(GLOBAL_FLAGS.get("scan_layers")),
        **opt_probe,
        **serving_probe,
        **spec_probe,
        **input_pipeline_probe,
        **gspmd_probe,
        **pipeline_probe,
        **fusion_probe,
        **tracing_probe,
        **telemetry_probe,
        **persistence_probe,
        **kv_tier_probe,
        **disagg_probe,
        **multitenant_probe,
        **megakernel_probe,
    }


def _peak_hbm_bytes(dev):
    """Peak device-memory bytes via PJRT memory_stats when available;
    None (JSON null) otherwise — a missing probe must read as missing."""
    try:
        stats = dev.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    for k in ("peak_bytes_in_use", "bytes_in_use"):
        if k in stats:
            return int(stats[k])
    return None


def _child_main():
    progress = _Progress()
    progress.mark("child_start", argv=sys.argv[1:])
    cfg = "llama_1b" if "--config=llama_1b" in sys.argv else "llama_125m"
    try:
        result = run_bench(cfg, progress)
        print(_SENTINEL + json.dumps(result))
        sys.exit(0)
    except Exception as e:  # noqa: BLE001 — reported via sentinel line
        import traceback
        traceback.print_exc(limit=8)
        progress.mark("child_error", error=f"{type(e).__name__}: {e}")
        print(_SENTINEL + json.dumps({"error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)


def _read_progress(path):
    """Parse the child's heartbeat file into a compact stage trail."""
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return []


def _stage_ms(stages):
    """Per-stage elapsed ms from the heartbeat trail: how long the child
    spent IN each stage (delta to the next mark; the last stage's
    duration is unknown — the child died or finished inside it — and
    reads null, never fabricated)."""
    out = []
    for i, s in enumerate(stages):
        t1 = stages[i + 1].get("t") if i + 1 < len(stages) else None
        out.append({
            "stage": s.get("stage"),
            "ms": round((t1 - s.get("t", 0.0)) * 1e3, 1)
            if t1 is not None else None,
        })
    return out


def _backend_probe_budget() -> float:
    """The backend probe's own sub-timeout: a jax.devices() that never
    returns hangs in native code and would otherwise burn the WHOLE child
    budget (BENCH_r05: all 300 s died in backend_probing). A child still
    sitting in "backend_probing" past this budget is killed early and the
    parent falls through to the last-good artifact immediately — no
    retry, a backend that did not come up will not between tries."""
    return float(os.environ.get("PADDLE_TPU_BENCH_BACKEND_TIMEOUT", "90"))


def _run_child(budget, extra_args=()):
    """Run one bench child under a wall-clock budget.

    Returns (payload_or_None, error_str, stages). The progress file gives
    post-hoc forensics: a timeout with no "backend_up" stage is a backend
    that never came up; "backend_up" without "compiled" is a compile
    blowup. The child
    is watched while it runs: a stall inside the backend probe trips the
    shorter ``_backend_probe_budget`` instead of the full ``budget``.
    """
    progress_path = f"/tmp/paddle_tpu_bench_progress_{os.getpid()}_{time.time_ns()}"
    env = dict(os.environ, **{_PROGRESS_ENV: progress_path})
    backend_budget = _backend_probe_budget()
    out_path = progress_path + ".out"
    err_path = progress_path + ".err"
    try:
        # output goes to files, not pipes: the watcher loop must never
        # deadlock against a child blocked on a full pipe buffer
        with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
            child = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child",
                 *extra_args],
                stdout=out_f, stderr=err_f, text=True, env=env)
            t0 = time.monotonic()
            timed_out = backend_hang = False
            while True:
                try:
                    child.wait(timeout=2.0)
                    break
                except subprocess.TimeoutExpired:
                    pass
                elapsed = time.monotonic() - t0
                if elapsed > budget:
                    timed_out = True
                else:
                    stages = _read_progress(progress_path)
                    if stages and stages[-1]["stage"] == "backend_probing" \
                            and elapsed - stages[-1].get("t", 0.0) \
                            > backend_budget:
                        timed_out = backend_hang = True
                if timed_out:
                    child.kill()
                    try:
                        child.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass
                    stages = _read_progress(progress_path)
                    reached = stages[-1]["stage"] if stages else "none"
                    if backend_hang:
                        return (None,
                                f"backend probe exceeded its "
                                f"{backend_budget:g}s sub-timeout "
                                f"(last stage: {reached})", stages)
                    return (None, f"timeout after {budget}s "
                                  f"(last stage: {reached})", stages)
        with open(out_path) as f_out, open(err_path) as f_err:
            proc = subprocess.CompletedProcess(
                child.args, child.returncode, f_out.read(), f_err.read())
        stages = _read_progress(progress_path)
        for line in proc.stdout.splitlines():
            if line.startswith(_SENTINEL):
                payload = json.loads(line[len(_SENTINEL):])
                if "error" not in payload:
                    return payload, None, stages
                # keep the child's traceback visible for forensics
                sys.stderr.write(proc.stderr or "")
                return None, payload["error"], stages
        sys.stderr.write(proc.stderr or "")
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()
        err = tail[-1] if tail else f"child exited rc={proc.returncode}"
        return None, err, stages
    finally:
        for p in (progress_path, out_path, err_path):
            try:
                os.unlink(p)
            except OSError:
                pass


def _last_good_round():
    """Most recent real measurement, marked stale when used.

    Sources, newest wins: driver artifacts (BENCH_r*.json) and
    tools/bench_lastgood.json — in-session measurements recorded while
    the chip was reachable (a same-round measurement beats a rounds-old
    driver artifact). Used only
    when every attempt this round failed: the artifact then carries the
    last real number instead of a 0.0 that erases the evidence chain.
    """
    here = os.path.dirname(os.path.abspath(__file__))
    best = None
    for path in sorted(glob.glob(os.path.join(here, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                parsed = json.load(f).get("parsed") or {}
        except (OSError, ValueError):
            continue
        if parsed.get("value") and not parsed.get("stale"):
            m = re.search(r"BENCH_r\d+\.json$", path)
            best = (m.group(0) if m else os.path.basename(path)), parsed
    lastgood = os.path.join(here, "tools", "bench_lastgood.json")
    try:
        with open(lastgood) as f:
            blob = json.load(f)
        parsed = blob.get("parsed") or {}
        if parsed.get("value"):
            best = (f"tools/bench_lastgood.json "
                    f"({blob.get('recorded', 'undated')})", parsed)
    except (OSError, ValueError):
        pass
    return best


def main():
    # Budgets: first try must cover cold compile (60-120 s per docs/PERF.md
    # §5) + measurement (~60 s); the retry runs against the now-warm
    # persistent compilation cache. 600+300 keeps the worst case (both
    # tries burn their full budget) inside the driver's window while
    # leaving 3x headroom over a healthy cold compile.
    budgets = tuple(
        float(b) for b in
        os.environ.get("PADDLE_TPU_BENCH_BUDGETS", "600,300").split(","))
    last_err, last_stages = "unknown", []
    for budget in budgets:
        payload, err, stages = _run_child(budget)
        if payload is not None:
            # opportunistic second config: the >=1B-param point
            # (remat + bf16) the round-2 verdict asked for
            payload["llama_1b"] = _run_1b_config()
            payload["stage_ms"] = _stage_ms(stages)
            print(json.dumps(payload))
            return 0
        last_err, last_stages = err, stages
        if "backend probe exceeded" in (err or ""):
            # a backend that hung coming up will hang again: fall through
            # to the last-good artifact immediately instead of burning
            # the retry budget in the same native hang
            break
        time.sleep(5.0)
    # no measurement was made: the artifact says why (and replays the last
    # good number, marked stale), and the exit status says so too
    print(json.dumps(_failure_artifact(last_err, last_stages)))
    return 1


def _failure_artifact(last_err, last_stages):
    """Total-failure artifact: carry the last real measurement (marked
    stale, ``vs_baseline`` passed through unchanged) instead of a 0.0
    that erases the evidence chain. Fields measured per-run
    (compile_ms / peak_hbm_bytes / remat_policy / accumulate_steps) stay
    null here — a stale artifact must never fabricate a measurement the
    failed run did not make."""
    out = {
        "metric": "llama_125m_train_tokens_per_sec_per_chip",
        "value": 0.0,
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "error": last_err,
        "stages": [s.get("stage") for s in last_stages],
        "stage_ms": _stage_ms(last_stages),
        "compile_ms": None,
        "peak_hbm_bytes": None,
        "remat_policy": None,
        "accumulate_steps": None,
        # low-bit serving fields are measured per-run: a stale artifact
        # must carry nulls, never the stale round's numbers
        "quantized_mode": None,
        "weight_bytes": None,
        "kv_bytes_per_token": None,
        "quantized_decode_tokens_per_s": None,
        # ragged-serving fields likewise: compile counts and prefix-cache
        # behavior are per-run observations, never inherited from the
        # stale source
        "decode_compiles": None,
        "prefix_cache_hit_rate": None,
        "shared_page_fraction": None,
        # serving-latency percentiles (engine histograms) are per-run
        # measurements: a stale artifact must never carry a TTFT/TPOT
        # the failed run did not observe
        "serving_ttft_p50_ms": None,
        "serving_ttft_p99_ms": None,
        "serving_tpot_p50_ms": None,
        # burst/megakernel fields are per-run too: a stale artifact must
        # never claim a dispatch ratio or kernel mode the failed run
        # did not measure
        "burst_tokens": None,
        "host_dispatches_per_token": None,
        "megakernel_mode": None,
        "burst_tokens_per_s": None,
        # speculative-decoding fields are per-run measurements too: an
        # acceptance rate or launches-per-token ratio the failed run
        # never observed must stay null
        "spec_target_steps_per_token": None,
        "spec_accept_rate": None,
        "spec_decode_compiles": None,
        # gspmd sharding fields are per-run measurements (compile
        # counts, HLO collective mix, per-device KV bytes): null on a
        # stale artifact, never copied from the last good round
        "gspmd_train_compiles": None,
        "gspmd_allreduce_count": None,
        "gspmd_allgather_count": None,
        "gspmd_serving_decode_compiles": None,
        "gspmd_sharded_kv_bytes_per_token": None,
        # HLO fusion forensics are per-run compiler observations: a
        # stale artifact must never claim fusion/kernel counts the
        # failed run's compiler never produced
        "hlo_train_fusions": None,
        "hlo_train_kernels": None,
        "hlo_serving_fusions": None,
        "hlo_serving_kernels": None,
        "hlo_serving_fusion_bytes": None,
        # request-tracing fields are per-run observations too: a
        # determinism verdict or span count from a stale round proves
        # nothing about the run that failed
        "trace_deterministic": None,
        "trace_span_count": None,
        "trace_decode_compiles": None,
        # fleet-telemetry fields likewise: a scrape count, an alert
        # transition tally, or a byte-identity verdict from a stale
        # round proves nothing about the run that failed
        "telemetry_deterministic": None,
        "telemetry_scrape_samples": None,
        "telemetry_alerts_fired": None,
        "telemetry_alerts_resolved": None,
        "telemetry_decode_compiles": None,
        # crash-consistent persistence fields are per-run proofs: a
        # resume-identity verdict, fallback count, warm-hit count, or
        # save/restore timing from a stale round proves nothing about
        # the run that failed
        "persist_resume_identical": None,
        "persist_restore_fallbacks": None,
        "persist_warm_prefix_hits": None,
        "persist_ckpt_save_ms": None,
        "persist_ckpt_restore_ms": None,
        # two-tier KV fields are per-run proofs too: an over-capacity
        # token-identity verdict, spill/prefetch counts, a stall
        # fraction, or the tier page budgets from a stale round prove
        # nothing about the run that failed
        "kv_tier_token_identical": None,
        "kv_tier_spills": None,
        "kv_tier_prefetch_hits": None,
        "kv_tier_stall_fraction": None,
        "kv_tier_deterministic": None,
        "kv_tier_hbm_pages": None,
        "kv_tier_host_pages": None,
        # disaggregated-serving fields are per-run proofs too: a
        # token-identity verdict, fabric page count, fleet prefix hit
        # rate, or TTFT ratio from a stale round proves nothing about
        # the run that failed
        "disagg_token_identical": None,
        "disagg_kv_pages_transferred": None,
        "disagg_fleet_prefix_hit_rate": None,
        "disagg_transfer_stall_fraction": None,
        "disagg_ttft_ratio_vs_colocated": None,
        "disagg_deterministic": None,
        "disagg_ttft_p99_s": None,
        "disagg_colocated_ttft_p99_s": None,
        # multi-tenant economy fields are per-run proofs too: an
        # isolation ratio, quota-shed count, mixed-batch identity
        # verdict, or hot-swap compile count from a stale round proves
        # nothing about the run that failed
        "multitenant_good_ttft_p99_s": None,
        "multitenant_isolation_ratio": None,
        "multitenant_quota_shed": None,
        "multitenant_deterministic": None,
        "multitenant_mixed_batch_identical": None,
        "multitenant_hot_swap_compiles": None,
        # whole-model megakernel fields are per-run structural proofs:
        # a launches-per-token count, scope bit, token-identity
        # verdict, or compiled fusion/kernel count from a stale round
        # proves nothing about the run that failed
        "mk_model_scope": None,
        "mk_launches_per_token": None,
        "mk_burst_launches_per_token": None,
        "mk_token_identity": None,
        "mk_serving_fusions": None,
        "mk_serving_kernels": None,
        # fused ragged-prefill fields likewise: compiled counts, the
        # bitwise-identity verdict, launches-per-chunk, and the
        # virtual-clock flood numbers are all per-run proofs
        "mk_prefill_fusions": None,
        "mk_prefill_kernels": None,
        "mk_prefill_token_identity": None,
        "mk_prefill_launches_per_chunk": None,
        "mk_prefill_ttft_p99_s": None,
        "mk_prefill_ttft_ratio_vs_unfused": None,
        "mk_prefill_tokens_per_s": None,
        "mk_prefill_decode_tokens": None,
        # pipeline-parallel fields are per-run structural proofs: a
        # loss-parity verdict, stage-ring permute count, max-stage
        # param fraction, or bubble fraction from a stale round proves
        # nothing about the run that failed
        "pipeline_loss_parity": None,
        "pipeline_ring_permutes": None,
        "pipeline_dp_ring_permutes": None,
        "pipeline_max_stage_param_fraction": None,
        "pipeline_bubble_fraction": None,
        "pipeline_train_compiles": None,
    }
    good = _last_good_round()
    if good:
        src, parsed = good
        out.update({k: parsed[k] for k in
                    ("value", "vs_baseline", "mfu", "device", "step_ms")
                    if k in parsed})
        out["stale"] = True
        out["stale_source"] = src
    return out


def _run_1b_config():
    budget = float(os.environ.get("PADDLE_TPU_BENCH_1B_BUDGET", "900"))
    payload, err, stages = _run_child(budget, ("--config=llama_1b",))
    if payload is not None:
        return payload
    return {"error": err, "stages": [s.get("stage") for s in stages]}


if __name__ == "__main__":
    if "--child" in sys.argv:
        _child_main()
    else:
        sys.exit(main())
