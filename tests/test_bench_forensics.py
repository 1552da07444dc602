"""bench.py forensic stages (round-5 verdict item 4): a backend that never
comes up must be RECORDED in the artifact, not inferred — the child marks
"backend_probing" immediately before the first backend touch, so a
timeout whose last stage is backend_probing conclusively names backend
init as the stall.
"""
import os
import sys

import pytest


@pytest.mark.slow
def test_simulated_backend_hang_names_the_stage():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench

    env_keys = {
        "PADDLE_TPU_BENCH_SIMULATE_HANG": "backend",
        "JAX_PLATFORMS": "cpu",
    }
    old = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    try:
        # the child must finish its imports within the budget even on a
        # loaded single-core box — the hang then burns the remainder
        payload, err, stages = bench._run_child(90.0)
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert payload is None
    assert "timeout" in err and "backend_probing" in err, (err, stages)
    names = [s.get("stage") for s in stages]
    assert names[-1] == "backend_probing", names
    assert "imports_done" in names     # the stall is AFTER imports


def test_stale_artifact_nulls_per_run_fields(monkeypatch):
    """Round-6: when every attempt failed and the artifact falls back to
    stale data, ``vs_baseline`` passes through from the stale source
    unchanged, but fields measured per-run (compile_ms, peak_hbm_bytes,
    remat_policy, accumulate_steps) must be null — a stale artifact must
    never fabricate a measurement the failed run did not make (BENCH_r05
    is such a stale-source run)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench

    stale_parsed = {"value": 70000.0, "vs_baseline": 0.8333, "mfu": 0.375,
                    "device": "TPU v5 lite", "step_ms": 110.0,
                    "compile_ms": 1234.5, "peak_hbm_bytes": 7 << 30,
                    "remat_policy": "full", "accumulate_steps": 4,
                    # a stale source CARRYING latency numbers must not
                    # leak them into the fresh artifact
                    "serving_ttft_p50_ms": 12.0,
                    "serving_tpot_p50_ms": 3.5}
    monkeypatch.setattr(bench, "_last_good_round",
                        lambda: ("BENCH_r05.json", stale_parsed))
    out = bench._failure_artifact(
        "timeout after 600s",
        [{"stage": "imports_done", "t": 1.0},
         {"stage": "backend_probing", "t": 2.5}])
    assert out["stale"] is True
    assert out["stale_source"] == "BENCH_r05.json"
    assert out["vs_baseline"] == 0.8333          # unchanged pass-through
    assert out["value"] == 70000.0
    for k in ("compile_ms", "peak_hbm_bytes", "remat_policy",
              "accumulate_steps", "quantized_mode", "weight_bytes",
              "kv_bytes_per_token", "quantized_decode_tokens_per_s",
              # ragged-serving fields are per-run observations too: a
              # stale artifact must not claim a compile count or a
              # prefix-cache hit rate the failed run never measured
              "decode_compiles", "prefix_cache_hit_rate",
              "shared_page_fraction",
              # burst/megakernel fields likewise (PR 7): a dispatch
              # ratio or kernel mode is a per-run measurement
              "burst_tokens", "host_dispatches_per_token",
              "megakernel_mode", "burst_tokens_per_s",
              # serving-latency percentiles (PR 8, engine histograms):
              # a stale artifact must never carry a TTFT/TPOT the
              # failed run did not observe — and never copy one from
              # tools/bench_lastgood.json
              "serving_ttft_p50_ms", "serving_ttft_p99_ms",
              "serving_tpot_p50_ms",
              # speculative-decoding fields (PR 9): acceptance rate and
              # launches-per-token are per-run measurements
              "spec_target_steps_per_token", "spec_accept_rate",
              "spec_decode_compiles",
              # gspmd sharding fields (PR 10): compile counts, HLO
              # collective mix and per-device KV bytes are per-run
              "gspmd_train_compiles", "gspmd_allreduce_count",
              "gspmd_allgather_count", "gspmd_serving_decode_compiles",
              "gspmd_sharded_kv_bytes_per_token",
              # HLO fusion forensics + tracing fields (PR 12): fusion/
              # kernel counts are compiler observations of THIS run,
              # and a determinism verdict from a stale round proves
              # nothing about the run that failed
              "hlo_train_fusions", "hlo_train_kernels",
              "hlo_serving_fusions", "hlo_serving_kernels",
              "hlo_serving_fusion_bytes",
              "trace_deterministic", "trace_span_count",
              "trace_decode_compiles",
              # fleet-telemetry fields (PR 13): scrape counts, alert
              # transitions and the determinism verdict are per-run
              # observations — a stale round proves nothing here
              "telemetry_deterministic", "telemetry_scrape_samples",
              "telemetry_alerts_fired", "telemetry_alerts_resolved",
              "telemetry_decode_compiles",
              # crash-consistent persistence fields (ISSUE 14): a
              # resume-identity verdict, restore fallback count, warm-
              # hit count or save/restore timing is a per-run proof
              "persist_resume_identical", "persist_restore_fallbacks",
              "persist_warm_prefix_hits", "persist_ckpt_save_ms",
              "persist_ckpt_restore_ms",
              # two-tier KV fields (ISSUE 15): the over-capacity
              # token-identity verdict, spill/prefetch counts, stall
              # fraction and tier budgets are per-run proofs
              "kv_tier_token_identical", "kv_tier_spills",
              "kv_tier_prefetch_hits", "kv_tier_stall_fraction",
              "kv_tier_deterministic", "kv_tier_hbm_pages",
              "kv_tier_host_pages",
              # disaggregated-serving fields (ISSUE 16): identity
              # verdicts, fabric page counts and TTFT ratios are
              # per-run proofs
              "disagg_token_identical", "disagg_kv_pages_transferred",
              "disagg_fleet_prefix_hit_rate",
              "disagg_transfer_stall_fraction",
              "disagg_ttft_ratio_vs_colocated", "disagg_deterministic",
              "disagg_ttft_p99_s", "disagg_colocated_ttft_p99_s",
              # multi-tenant economy fields (ISSUE 17): an isolation
              # ratio, quota-shed count, mixed-batch identity verdict
              # or hot-swap compile count is a per-run proof
              "multitenant_good_ttft_p99_s",
              "multitenant_isolation_ratio", "multitenant_quota_shed",
              "multitenant_deterministic",
              "multitenant_mixed_batch_identical",
              "multitenant_hot_swap_compiles",
              # whole-model megakernel fields (ISSUE 18): a
              # launches-per-token count, scope bit, token-identity
              # verdict or compiled fusion/kernel count is a per-run
              # structural proof
              "mk_model_scope", "mk_launches_per_token",
              "mk_burst_launches_per_token", "mk_token_identity",
              "mk_serving_fusions", "mk_serving_kernels",
              # fused ragged-prefill fields (ISSUE 20): compiled
              # counts, the bitwise-identity verdict, launches-per-
              # chunk and the virtual-clock flood numbers are per-run
              # structural proofs
              "mk_prefill_fusions", "mk_prefill_kernels",
              "mk_prefill_token_identity",
              "mk_prefill_launches_per_chunk", "mk_prefill_ttft_p99_s",
              "mk_prefill_ttft_ratio_vs_unfused",
              "mk_prefill_tokens_per_s", "mk_prefill_decode_tokens",
              # pipeline-parallel fields (ISSUE 19): a loss-parity
              # verdict, stage-ring permute count, max-stage param
              # fraction or bubble fraction is a per-run structural
              # proof
              "pipeline_loss_parity", "pipeline_ring_permutes",
              "pipeline_dp_ring_permutes",
              "pipeline_max_stage_param_fraction",
              "pipeline_bubble_fraction", "pipeline_train_compiles"):
        assert out[k] is None, k                 # never fabricated
    # per-stage elapsed ms: delta to the next mark; the stage the child
    # died inside has no known duration -> null
    assert out["stage_ms"] == [
        {"stage": "imports_done", "ms": 1500.0},
        {"stage": "backend_probing", "ms": None}]
    # and with no stale source at all, the nulls (and 0.0) survive
    monkeypatch.setattr(bench, "_last_good_round", lambda: None)
    out = bench._failure_artifact("err", [])
    assert out["value"] == 0.0 and out["compile_ms"] is None
    assert "stale" not in out


def test_backend_probe_sub_timeout(monkeypatch):
    """A child stuck in backend_probing is killed after the probe's OWN
    sub-timeout, not the full child budget (BENCH_r05: the whole 300 s
    died in backend_probing), and the error names the sub-timeout so
    main() falls through to the last-good artifact without a retry."""
    import time
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench

    env_keys = {
        "PADDLE_TPU_BENCH_SIMULATE_HANG": "backend",
        "PADDLE_TPU_BENCH_BACKEND_TIMEOUT": "6",
        "JAX_PLATFORMS": "cpu",
    }
    old = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    try:
        t0 = time.monotonic()
        payload, err, stages = bench._run_child(300.0)
        elapsed = time.monotonic() - t0
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert payload is None
    assert "backend probe exceeded" in err, (err, stages)
    assert "backend_probing" in err
    assert elapsed < 120, f"sub-timeout did not trip early ({elapsed}s)"


def test_peak_hbm_probe_never_fabricates():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench

    class NoStats:
        def memory_stats(self):
            raise NotImplementedError

    class EmptyStats:
        def memory_stats(self):
            return {}

    class WithPeak:
        def memory_stats(self):
            return {"peak_bytes_in_use": 123, "bytes_in_use": 7}

    assert bench._peak_hbm_bytes(NoStats()) is None
    assert bench._peak_hbm_bytes(EmptyStats()) is None
    assert bench._peak_hbm_bytes(WithPeak()) == 123


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def _main_with_children(monkeypatch, capsys, child_result):
    """Run bench.main() with the child processes replaced by a canned
    (payload, error, stages) result; returns (exit status, artifact)."""
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench
    import copy
    monkeypatch.setattr(
        bench, "_run_child",
        lambda budget, extra_args=(): copy.deepcopy(child_result))
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("PADDLE_TPU_BENCH_BUDGETS", "1,1")
    rc = bench.main()
    return rc, json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("case", [
    "unknown_device_kind_raises", "cpu_has_no_peak", "known_kinds",
    "total_failure_exits_nonzero", "measurement_exits_zero"])
def test_bench_refuses_what_it_did_not_measure(case, monkeypatch, capsys):
    """No fallback that hides the device: a device kind with no peak on
    record is an error (there is no nominal "cpu" peak to divide by),
    and a run that made no measurement exits non-zero even though its
    artifact replays the last good number, marked stale."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench
    if case == "unknown_device_kind_raises":
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            bench.peak_flops(_Dev("TPU v9 imaginary"))
    elif case == "cpu_has_no_peak":
        assert "cpu" not in bench.PEAK_FLOPS
        with pytest.raises(ValueError, match="'cpu'"):
            bench.peak_flops(_Dev("cpu"))
    elif case == "known_kinds":
        assert bench.peak_flops(_Dev("TPU v5 lite")) == 197e12
        assert bench.peak_flops(_Dev("TPU v5p")) == 459e12
    elif case == "total_failure_exits_nonzero":
        rc, art = _main_with_children(
            monkeypatch, capsys,
            (None, "timeout after 1s (last stage: backend_probing)",
             [{"stage": "backend_probing", "t": 0.5}]))
        assert rc == 1
        assert art["error"].startswith("timeout")
        assert art["compile_ms"] is None        # nothing was measured
    else:
        rc, art = _main_with_children(
            monkeypatch, capsys,
            ({"metric": "m", "value": 1.0}, None,
             [{"stage": "measured", "t": 1.0}]))
        assert rc == 0 and art["value"] == 1.0 and "stale" not in art


def _proxy_bench():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import tools.proxy_bench as pb
    return pb


def test_proxy_bench_gate_logic():
    """Direction-aware gate: counts regress upward, rates regress
    downward, a null measurement where the baseline has a number is a
    failure (a probe that stopped measuring is coverage loss), and a
    metric missing from a FULL run fails while partial --probes runs
    skip it."""
    pb = _proxy_bench()
    base = {"metrics": {"decode_compiles": 1,
                        "host_dispatches_per_token": 0.2,
                        "prefix_cache_hit_rate": 0.8}}
    ok = {"metrics": {"decode_compiles": 1,
                      "host_dispatches_per_token": 0.21,
                      "prefix_cache_hit_rate": 0.78}}
    failures, report = pb.gate(ok, base)
    assert failures == [], report

    worse = {"metrics": {"decode_compiles": 2,
                         "host_dispatches_per_token": 1.0,
                         "prefix_cache_hit_rate": 0.3}}
    failures, report = pb.gate(worse, base)
    assert sorted(n for n, _ in failures) == [
        "decode_compiles", "host_dispatches_per_token",
        "prefix_cache_hit_rate"]
    assert "REGRESSION" in report

    broke = {"metrics": {"decode_compiles": 1,
                         "host_dispatches_per_token": None,
                         "prefix_cache_hit_rate": 0.8}}
    failures, report = pb.gate(broke, base)
    assert [n for n, _ in failures] == ["host_dispatches_per_token"]
    assert "PROBE BROKE" in report

    gone = {"metrics": {"decode_compiles": 1}}
    failures, _ = pb.gate(gone, base)
    assert sorted(n for n, _ in failures) == [
        "host_dispatches_per_token", "prefix_cache_hit_rate"]
    failures, _ = pb.gate(gone, base, require_all=False)
    assert failures == []


def test_proxy_bench_compare_exit_status(monkeypatch, capsys, tmp_path):
    """The compare mode's CLI contract against the CHECKED-IN baseline:
    parity exits 0, a regressed metric exits 1 (what CI keys off)."""
    import copy
    import json as _json
    pb = _proxy_bench()
    with open(pb.BASELINE_PATH) as f:
        base = _json.load(f)["cpu"]

    parity = copy.deepcopy(base)
    monkeypatch.setattr(pb, "collect",
                        lambda probes=pb.PROBES, **kw: parity)
    assert pb.main(["--compare", pb.BASELINE_PATH]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out

    regressed = copy.deepcopy(base)
    # the injected regression: burst mode degenerating to one host
    # dispatch per token (exactly what forcing the per-token path does)
    regressed["metrics"]["host_dispatches_per_token"] = 1.0
    monkeypatch.setattr(pb, "collect",
                        lambda probes=pb.PROBES, **kw: regressed)
    assert pb.main(["--compare", pb.BASELINE_PATH]) == 1
    captured = capsys.readouterr()
    assert "host_dispatches_per_token" in captured.err

    # a missing baseline file / backend is operator error, rc 2
    assert pb.main(["--compare", "/nonexistent/baseline.json"]) == 2

    # --json changes the output format, never the gate: the regressed
    # run still exits 1, stdout is PURE collection JSON (parseable),
    # and the human gate report moves to stderr
    assert pb.main(["--compare", pb.BASELINE_PATH, "--json"]) == 1
    captured = capsys.readouterr()
    parsed = _json.loads(captured.out)          # whole stream is JSON
    assert parsed["metrics"]["host_dispatches_per_token"] == 1.0
    assert "proxy bench gate" in captured.err

    # --record over a partial probe set would shrink the checked-in
    # baseline (silent coverage loss on every later compare): refused
    assert pb.main(["--probes", "serving", "--record"]) == 2
    assert "full probe set" in capsys.readouterr().err

    # --record --compare would "verify" a baseline against itself: out
    assert pb.main(["--record", "--compare", pb.BASELINE_PATH]) == 2
    assert "mutually exclusive" in capsys.readouterr().err

    # --record of a collection with a broken probe (null metric) would
    # drop that metric from every later compare's coverage: refused
    # (BASELINE_PATH redirected so a refusal bug cannot clobber the
    # checked-in baseline)
    monkeypatch.setattr(pb, "BASELINE_PATH", str(tmp_path / "b.json"))
    broken = copy.deepcopy(base)
    broken["metrics"]["host_dispatches_per_token"] = None
    broken["probe_errors"] = {"serving_probe_error": "boom"}
    monkeypatch.setattr(pb, "collect",
                        lambda probes=pb.PROBES, **kw: broken)
    assert pb.main(["--record"]) == 2
    assert "refusing to record" in capsys.readouterr().err


def test_proxy_bench_catches_forced_per_token_dispatch():
    """End-to-end regression injection (the acceptance bar): actually
    run the serving probe with the burst loop FORCED to the per-token
    dispatch path (burst_tokens=1) and gate it against the checked-in
    baseline — host_dispatches_per_token must rise past the bound and
    fail; the healthy collection of the same probe must pass."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("serving",), burst_tokens=1)
    failures, report = pb.gate(bad, baseline, require_all=False)
    assert "host_dispatches_per_token" in [n for n, _ in failures], report

    good = pb.collect(probes=("serving",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report


def test_serving_probe_records_ragged_and_prefix_fields():
    """The live serving probe must measure the ragged-engine fields:
    exactly one compiled step executable, a real prefix-cache hit rate
    from the staggered shared-prefix wave, and a nonzero peak
    shared-page fraction — and its total-failure fallback must null them
    instead of fabricating."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import bench
    import paddle_tpu as paddle

    out = bench._probe_serving(paddle, wave=4, max_new=3)
    assert "serving_probe_error" not in out, out
    assert out["decode_compiles"] == 1, out
    assert out["prefix_cache_hit_rate"] is not None
    assert 0.0 < out["prefix_cache_hit_rate"] <= 1.0
    assert out["shared_page_fraction"] > 0.0
    assert out["serving_tokens_per_s"] > 0.0
    # engine-histogram latency fields (PR 8): measured, not fabricated
    assert out["serving_ttft_p50_ms"] is not None
    assert out["serving_ttft_p99_ms"] is not None
    assert out["serving_tpot_p50_ms"] is not None
    assert 0 < out["serving_ttft_p50_ms"] <= out["serving_ttft_p99_ms"]
    # the burst wave measured the on-device token loop: dispatch ratio
    # well under one per token, mode named (jnp on this CPU container)
    assert "burst_probe_error" not in out, out
    assert out["burst_tokens"] == 8
    assert out["host_dispatches_per_token"] is not None
    assert out["host_dispatches_per_token"] < 0.8, out
    assert out["megakernel_mode"] in ("pallas", "interpret", "jnp")
    assert out["burst_tokens_per_s"] > 0.0


def test_proxy_bench_catches_disabled_speculation():
    """End-to-end spec regression injection: run the spec probe with the
    draft DISABLED (spec_tokens=0) and gate against the checked-in
    baseline — target launches per committed token rise to exactly 1.0
    and acceptance collapses, both past their bounds; the healthy
    collection of the same probe must pass."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("spec",), spec_tokens=0)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "spec_target_steps_per_token" in names
    assert "spec_accept_rate" in names
    assert bad["metrics"]["spec_target_steps_per_token"] == 1.0

    good = pb.collect(probes=("spec",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["spec_target_steps_per_token"] < 1.0
    assert good["metrics"]["spec_decode_compiles"] == 1


def test_proxy_bench_catches_forced_dp_only_regime():
    """End-to-end gspmd regression injection: run the gspmd probe with
    the regime FORCED to data-parallel-only (no model axis) and gate
    against the checked-in baseline — per-device sharded KV bytes/token
    double past the exact bound and fail; the healthy collection of the
    same probe must pass."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("gspmd",), gspmd_dp_only=True)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "gspmd_sharded_kv_bytes_per_token" in names
    assert bad["metrics"]["gspmd_sharded_kv_bytes_per_token"] == \
        2 * baseline["metrics"]["gspmd_sharded_kv_bytes_per_token"]

    good = pb.collect(probes=("gspmd",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["gspmd_train_compiles"] == 1
    assert good["metrics"]["gspmd_serving_decode_compiles"] == 1


def test_spec_probe_never_fabricates_on_failure(monkeypatch):
    """A broken spec probe reports nulls plus an error field — never a
    fabricated acceptance rate."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_spec_decode(Boom())
    assert out["spec_target_steps_per_token"] is None
    assert out["spec_accept_rate"] is None
    assert out["spec_decode_compiles"] is None
    assert "spec_decode_probe_error" in out


def test_proxy_bench_catches_defused_region():
    """End-to-end fusion regression injection (ISSUE 12): run the
    fusion probe with FLAGS_fusion_probe_barrier splitting the ragged
    layer's hot fused region and gate against the checked-in baseline —
    serving fusion/kernel counts and fused-region bytes all rise past
    their exact bounds; the healthy collection of the same probe must
    pass."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("fusion",), fusion_defuse=True)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "hlo_serving_fusions" in names
    assert "hlo_serving_kernels" in names
    assert "hlo_serving_fusion_bytes" in names
    assert bad["metrics"]["hlo_serving_fusions"] > \
        baseline["metrics"]["hlo_serving_fusions"]

    good = pb.collect(probes=("fusion",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    # the barrier flag must have been restored by the probe
    from paddle_tpu.core.flags import GLOBAL_FLAGS
    assert GLOBAL_FLAGS.get("fusion_probe_barrier") is False


def test_tracing_probe_gates_and_never_fabricates():
    """The tracing probe's healthy collection passes its exact gates
    (byte-identical export, pinned span count, one executable); a
    broken probe reports nulls + an error field."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    good = pb.collect(probes=("tracing",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["trace_deterministic"] == 1
    assert good["metrics"]["trace_decode_compiles"] == 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_tracing(Boom())
    assert out["trace_deterministic"] is None
    assert out["trace_span_count"] is None
    assert "tracing_probe_error" in out


def test_proxy_bench_catches_disabled_burn_alerts():
    """End-to-end telemetry regression injection (ISSUE 13): run the
    telemetry probe with the burn-rate rules dropped (--no-burn-alerts)
    and gate against the checked-in baseline — the seeded slowdown
    fault then fires (and resolves) nothing, both alert counts read 0,
    and the exact gates fail; the healthy collection of the same probe
    must pass."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("telemetry",), telemetry_burn_alerts=False)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "telemetry_alerts_fired" in names
    assert "telemetry_alerts_resolved" in names
    assert bad["metrics"]["telemetry_alerts_fired"] == 0

    good = pb.collect(probes=("telemetry",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["telemetry_deterministic"] == 1
    assert good["metrics"]["telemetry_alerts_fired"] >= 1
    assert good["metrics"]["telemetry_alerts_resolved"] >= 1
    assert good["metrics"]["telemetry_decode_compiles"] == 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_telemetry(Boom())
    assert out["telemetry_deterministic"] is None
    assert out["telemetry_alerts_fired"] is None
    assert "telemetry_probe_error" in out


def test_proxy_bench_catches_corrupt_checkpoint():
    """End-to-end persistence regression injection (ISSUE 14): run the
    persistence probe with every stored version byte-flipped
    (--corrupt-checkpoint) and gate against the checked-in baseline —
    the training resume diverges (identity verdict 0), the prefix
    restore degrades to a cold start (warm hits 0, fallbacks >= 1),
    and all three exact gates fail; the healthy collection of the same
    probe must pass."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("persist",), persist_corrupt=True)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "persist_resume_identical" in names
    assert "persist_restore_fallbacks" in names
    assert "persist_warm_prefix_hits" in names
    assert bad["metrics"]["persist_resume_identical"] == 0
    assert bad["metrics"]["persist_warm_prefix_hits"] == 0

    good = pb.collect(probes=("persist",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["persist_resume_identical"] == 1
    assert good["metrics"]["persist_restore_fallbacks"] == 0
    assert good["metrics"]["persist_warm_prefix_hits"] >= 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_persistence(Boom())
    assert out["persist_resume_identical"] is None
    assert out["persist_warm_prefix_hits"] is None
    assert "persistence_probe_error" in out


def test_proxy_bench_catches_disabled_fairness():
    """End-to-end multi-tenant regression injection (ISSUE 17): run the
    multitenant probe with the tenant policy dropped (--no-fairness:
    bare FIFO over the same noisy-neighbor flood) and gate against the
    checked-in baseline — quota sheds read 0 (exact pin), the good
    tenant's p99 TTFT blows out behind the abuser's backlog, and the
    isolation ratio collapses toward 1; all three gates fail. The
    healthy collection of the same probe must pass with sheds pinned,
    the mixed LoRA/base batch bit-identical to the no-adapter engine,
    and adapter hot-swap adding zero decode executables."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("multitenant",), multitenant_fairness=False)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "multitenant_quota_shed" in names
    assert "multitenant_good_ttft_p99_s" in names
    assert "multitenant_isolation_ratio" in names
    assert bad["metrics"]["multitenant_quota_shed"] == 0
    # the rc-level contract CI keys off: --no-fairness flips main to 1
    import unittest.mock as _mock
    with _mock.patch.object(pb, "collect",
                            lambda probes=pb.PROBES, **kw: bad):
        assert pb.main(["--probes", "multitenant", "--compare",
                        pb.BASELINE_PATH]) == 1

    good = pb.collect(probes=("multitenant",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["multitenant_quota_shed"] == \
        baseline["metrics"]["multitenant_quota_shed"]
    assert good["metrics"]["multitenant_isolation_ratio"] < 0.5
    assert good["metrics"]["multitenant_deterministic"] == 1
    assert good["metrics"]["multitenant_mixed_batch_identical"] == 1
    assert good["metrics"]["multitenant_hot_swap_compiles"] == 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_multitenant(Boom())
    assert out["multitenant_isolation_ratio"] is None
    assert out["multitenant_quota_shed"] is None
    assert "multitenant_probe_error" in out


def test_proxy_bench_catches_forced_per_layer_scope():
    """End-to-end megakernel regression injection (ISSUE 18): run the
    megakernel probe with the measured engine FORCED back to layer
    scope (--per-layer) and gate against the checked-in baseline —
    the scope bit reads 0, launches per token rise from 1.0 to
    num_layers, the burst ratio triples, the compiled ragged step's
    fusion/kernel counts rise; five gates fail. The healthy collection
    of the same probe must pass with the layer body appearing ONCE in
    the program and tokens bitwise identical between scopes."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("megakernel",), megakernel_per_layer=True)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "mk_model_scope" in names
    assert "mk_launches_per_token" in names
    assert "mk_burst_launches_per_token" in names
    assert "mk_serving_fusions" in names
    assert "mk_serving_kernels" in names
    assert bad["metrics"]["mk_model_scope"] == 0
    assert bad["metrics"]["mk_launches_per_token"] > 1.0
    # the rc-level contract CI keys off: --per-layer flips main to 1
    import unittest.mock as _mock
    with _mock.patch.object(pb, "collect",
                            lambda probes=pb.PROBES, **kw: bad):
        assert pb.main(["--probes", "megakernel", "--compare",
                        pb.BASELINE_PATH]) == 1

    good = pb.collect(probes=("megakernel",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["mk_model_scope"] == 1
    assert good["metrics"]["mk_launches_per_token"] == 1.0
    assert good["metrics"]["mk_burst_launches_per_token"] < 1.0
    assert good["metrics"]["mk_token_identity"] == 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_megakernel(Boom())
    assert out["mk_launches_per_token"] is None
    assert out["mk_token_identity"] is None
    assert out["mk_prefill_fusions"] is None
    assert out["mk_prefill_token_identity"] is None
    assert out["mk_prefill_ttft_ratio_vs_unfused"] is None
    assert "megakernel_probe_error" in out


def test_proxy_bench_catches_unfused_prefill():
    """End-to-end fused-prefill regression injection (ISSUE 20): run
    the megakernel probe with the fused-prefill measurement's engine
    built UNFUSED (--per-layer-prefill) and gate against the
    checked-in baseline — the compiled ragged-step counts climb back
    to the unfused mk_serving_* floor, the long-prompt-flood TTFT
    ratio reads 1.0 against its < 1 baseline, flood throughput drops;
    five gates fail and main() exits 1. The healthy collection must
    pass with the fused compiled counts strictly BELOW the unfused
    floor, tokens bitwise identical, and decode progress pinned."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("megakernel",),
                     megakernel_per_layer_prefill=True)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "mk_prefill_fusions" in names
    assert "mk_prefill_kernels" in names
    assert "mk_prefill_ttft_p99_s" in names
    assert "mk_prefill_ttft_ratio_vs_unfused" in names
    assert "mk_prefill_tokens_per_s" in names
    assert bad["metrics"]["mk_prefill_ttft_ratio_vs_unfused"] == 1.0
    assert bad["metrics"]["mk_prefill_fusions"] == \
        bad["metrics"]["mk_serving_fusions"]
    # the rc-level contract CI keys off: --per-layer-prefill flips
    # main to 1
    import unittest.mock as _mock
    with _mock.patch.object(pb, "collect",
                            lambda probes=pb.PROBES, **kw: bad):
        assert pb.main(["--probes", "megakernel", "--per-layer-prefill",
                        "--compare", pb.BASELINE_PATH]) == 1

    good = pb.collect(probes=("megakernel",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    m = good["metrics"]
    # the headline: fused compiled counts strictly below the unfused
    # serving floor, identity bitwise, one launch covering every chunk
    # the step packs, and the flood actually decoded
    assert m["mk_prefill_fusions"] < m["mk_serving_fusions"]
    assert m["mk_prefill_kernels"] < m["mk_serving_kernels"]
    assert m["mk_prefill_token_identity"] == 1
    assert m["mk_prefill_launches_per_chunk"] <= 1.0
    assert m["mk_prefill_ttft_ratio_vs_unfused"] < 1.0
    assert m["mk_prefill_decode_tokens"] > 0


def test_proxy_bench_catches_disabled_kv_prefetch():
    """End-to-end two-tier KV regression injection (ISSUE 15): run the
    kvtier probe with the cursor-ahead staging disabled
    (--no-prefetch) and gate against the checked-in baseline — every
    parked-sequence restore becomes a counted stall (fraction 1.0 vs
    the 0.0 bound), prefetch hits collapse to 0 (exact pin), both
    gates fail; the healthy collection of the same probe must pass
    with spills > 0 and token identity intact."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("kvtier",), kvtier_prefetch=False)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "kv_tier_prefetch_hits" in names
    assert "kv_tier_stall_fraction" in names
    # even with prefetch off, restores land exact bytes: identity holds
    assert bad["metrics"]["kv_tier_token_identical"] == 1
    assert bad["metrics"]["kv_tier_prefetch_hits"] == 0
    assert bad["metrics"]["kv_tier_stall_fraction"] == 1.0

    good = pb.collect(probes=("kvtier",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["kv_tier_token_identical"] == 1
    assert good["metrics"]["kv_tier_spills"] > 0
    assert good["metrics"]["kv_tier_prefetch_hits"] > 0
    assert good["metrics"]["kv_tier_stall_fraction"] == 0.0
    assert good["metrics"]["kv_tier_deterministic"] == 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_kv_tiering(Boom())
    assert out["kv_tier_token_identical"] is None
    assert out["kv_tier_spills"] is None
    assert "kv_tiering_probe_error" in out


def test_proxy_bench_catches_disabled_pipeline():
    """End-to-end pipeline-parallel regression injection (ISSUE 19):
    run the pipeline probe with the stage axis disabled
    (--no-pipeline: pp=1 gradient accumulation at the SAME microbatch
    count) and gate against the checked-in baseline — the stage-ring
    collective-permute counts read 0 (exact two-sided pin vs the
    structural 5), the max-stage param fraction reads 1.0 (no stage
    owns less than everything), the analytic bubble fraction reads 0;
    four gates fail. The healthy collection of the same probe must
    pass with loss parity intact, exactly 5 ring permutes in both the
    pp=2 and dp=2,pp=2 programs, and ONE staged executable."""
    pb = _proxy_bench()
    import json as _json
    with open(pb.BASELINE_PATH) as f:
        baseline = _json.load(f)["cpu"]

    bad = pb.collect(probes=("pipeline",), pipeline_no_pp=True)
    names = [n for n, _ in pb.gate(bad, baseline, require_all=False)[0]]
    assert "pipeline_ring_permutes" in names
    assert "pipeline_dp_ring_permutes" in names
    assert "pipeline_max_stage_param_fraction" in names
    assert "pipeline_bubble_fraction" in names
    assert bad["metrics"]["pipeline_ring_permutes"] == 0
    assert bad["metrics"]["pipeline_max_stage_param_fraction"] == 1.0
    assert bad["metrics"]["pipeline_bubble_fraction"] == 0.0
    # the rc-level contract CI keys off: --no-pipeline flips main to 1
    import unittest.mock as _mock
    with _mock.patch.object(pb, "collect",
                            lambda probes=pb.PROBES, **kw: bad):
        assert pb.main(["--probes", "pipeline", "--compare",
                        pb.BASELINE_PATH]) == 1

    good = pb.collect(probes=("pipeline",))
    failures, report = pb.gate(good, baseline, require_all=False)
    assert failures == [], report
    assert good["metrics"]["pipeline_loss_parity"] == 1
    assert good["metrics"]["pipeline_ring_permutes"] == 5
    assert good["metrics"]["pipeline_dp_ring_permutes"] == 5
    assert good["metrics"]["pipeline_max_stage_param_fraction"] < 1.0
    assert 0.0 < good["metrics"]["pipeline_bubble_fraction"] < 1.0
    assert good["metrics"]["pipeline_train_compiles"] == 1

    import tools.bench_probes as bp

    class Boom:
        def seed(self, *_a):
            raise RuntimeError("boom")

    out = bp.probe_pipeline(Boom())
    assert out["pipeline_loss_parity"] is None
    assert out["pipeline_ring_permutes"] is None
    assert "pipeline_probe_error" in out
