"""CPU-tier proxy perf bench: chip-free regression gate over the
counted perf surfaces.

The flagship bench (bench.py) needs a live chip for tok/s and MFU
(BENCH_r03-r05 hold none: they are stale fallbacks).
This harness runs the measurements that DON'T need a chip and are
(near-)deterministic counts rather than timings:

- ``decode_compiles`` — ragged-step executables across a mixed serving
  wave (must stay 1: a jump is shape-dependent recompilation);
- ``host_dispatches_per_token`` — burst-mode serving dispatches per
  generated token (the on-device token loop's O(1)-per-burst contract;
  forcing the per-token path drives it toward >= 1);
- ``opt_dispatches_per_step`` — fused-optimizer dispatch count;
- ``host_syncs_per_epoch`` — async-pipeline blocking fetch rounds;
- ``fwd_jaxpr_eqns_scan`` / ``fwd_jaxpr_eqn_growth`` — trace size of the
  scanned forward and its growth with depth (must be 0);
- ``kv_bytes_per_token_fp32`` / ``_int8`` — exact KV pool byte
  accounting at a reference geometry;
- ``prefix_cache_hit_rate`` / ``shared_page_fraction`` — prefix-cache
  effectiveness over the shared-prefix wave (higher is better);
- ``cluster_goodput_fraction`` / ``cluster_retries`` /
  ``cluster_ttft_p99_s`` / ``cluster_unresolved`` — fleet robustness
  under a scripted kill-and-recover run (serving/cluster.py on the
  loadgen virtual clock; ``--no-retry`` is the injected regression);
- ``hlo_train_*`` / ``hlo_serving_*`` — fusion/kernel counts and
  bytes-touched-per-fused-region of the jitted TrainStep and the
  ragged serving step (jit/hlo_forensics.py; a defused hot region is
  silent 2x HBM traffic on chip — ``--defuse`` is the injected
  regression);
- ``trace_deterministic`` / ``trace_span_count`` /
  ``trace_decode_compiles`` — the request-tracing layer's contracts:
  byte-identical exports per seed and zero added step executables
  (serving/tracing.py);
- ``disagg_*`` — disaggregated prefill/decode serving contracts
  (serving/fabric.py + ClusterEngine roles): token identity vs a
  colocated fleet, KV pages actually moved over the fabric, fleet
  prefix hit rate with a crashed publisher, transfer stall fraction,
  byte-reproducible fleet reports, and the TTFT-p99 ratio vs
  colocated under a long-prompt flood (``--colocated`` is the
  injected regression);
- ``telemetry_*`` — the fleet time-series/SLO layer's contracts
  (paddle_tpu.telemetry): byte-identical series + alert-timeline
  exports per seed, a pinned scrape count, the seeded slowdown fault
  firing AND resolving its burn-rate alert (``--no-burn-alerts`` is
  the injected regression), and zero added step executables;
- ``multitenant_*`` — the multi-tenant serving economy's contracts
  (paddle_tpu.tenancy): noisy-neighbor p99 TTFT isolation under
  weighted-fair admission, exact quota-shed counts, byte-reproducible
  tenant reports, mixed-batch LoRA token identity over the int8 base,
  and adapter hot-swap with zero recompiles (``--no-fairness`` is the
  injected regression: bare FIFO over the same flood);
- ``pipeline_*`` — the pipeline-parallel stage axis's contracts
  (distributed/gspmd.py ``pp=K`` presets + the in-jit 1F1B microbatch
  loop): loss parity <= 1e-6 vs the single-device run for pp=2 and
  dp=2,pp=2, the stage-ring collective-permute count pinned both ways
  at its structural value, max-stage param byte fraction, the analytic
  bubble fraction (K-1)/(M+K-1) cross-checked against the schedule
  layout, and ONE staged TrainStep executable (``--no-pipeline`` is
  the injected regression: pp=1 gradient accumulation at the same
  microbatch count);
- ``mk_*`` — the whole-model decode megakernel's launch-collapse
  contracts (kernels/decode_megakernel.py ``fused_decode_model``): the
  decoder layer body appears ONCE in the ragged step's program
  (launches/token == 1.0 regardless of depth) and once per burst
  executable (1/burst_tokens), tokens stay bitwise identical to layer
  scope, and the compiled ragged step's fusion/kernel counts are
  pinned (``--per-layer`` is the injected regression: scope forced
  back to layer, launches/token rise to num_layers).

Each metric gates against a checked-in per-backend baseline
(tools/proxy_bench_baseline.json) with a direction and tolerance from
``GATES`` — a regression fails with rc 1, parity passes with rc 0, so
perf regressions surface in CI without a chip (docs/BENCH.md compares
these proxies with the chip metrics they predict).

Usage:
  python -m tools.proxy_bench                     # run, print JSON
  python -m tools.proxy_bench --record            # (re)record baseline
  python -m tools.proxy_bench --compare tools/proxy_bench_baseline.json
  python -m tools.proxy_bench --probes serving,jaxpr --compare ...

The probes themselves live in tools/bench_probes.py and are shared with
bench.py, which spreads the same fields into its flagship artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# the gspmd probe needs a multi-device mesh; force the 8-device
# host-CPU stand-in (the same environment tests/conftest.py pins for
# the whole suite — XLA parses XLA_FLAGS at backend creation, so this
# works as long as no device has been touched yet; on a real TPU the
# flag only affects the host platform)
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

BASELINE_PATH = os.path.join(REPO, "tools", "proxy_bench_baseline.json")

PROBES = ("serving", "spec", "gspmd", "cluster", "optimizer",
          "input_pipeline", "pipeline",
          "jaxpr", "accounting", "fusion", "tracing", "telemetry",
          "persist", "kvtier", "disagg", "multitenant", "megakernel")


class Gate:
    """Direction-aware tolerance: ``worse`` names the failing direction.

    higher-is-worse: fail when cur > base * (1 + rel) + abs
    lower-is-worse:  fail when cur < base * (1 - rel) - abs
    different-is-worse: fail when cur != base (exact two-sided pin —
    for counts where a DROP is as suspicious as a rise, e.g. the GSPMD
    collective mix: a rule-table miss that replicates params LOWERS the
    all-gather count).
    Counts gate tightly (rel 0, small abs); ratios get slack for
    environment drift. A None measurement where the baseline has a
    number is always a failure — a probe that stopped measuring is a
    silent coverage loss, not a pass.
    """

    def __init__(self, worse="higher", rel=0.0, abs_=0.0):
        assert worse in ("higher", "lower", "different")
        self.worse = worse
        self.rel = rel
        self.abs_ = abs_

    def bad(self, cur, base) -> bool:
        if self.worse == "different":
            return cur != base
        if self.worse == "higher":
            return cur > base * (1.0 + self.rel) + self.abs_
        return cur < base * (1.0 - self.rel) - self.abs_

    def bound(self, base) -> float:
        if self.worse in ("higher", "different"):
            return base * (1.0 + self.rel) + self.abs_
        return base * (1.0 - self.rel) - self.abs_


GATES = {
    "decode_compiles":          Gate("higher", 0.0, 0.0),
    "host_dispatches_per_token": Gate("higher", 0.20, 0.01),
    "opt_dispatches_per_step":  Gate("higher", 0.0, 2.0),
    "host_syncs_per_epoch":     Gate("higher", 0.0, 2.0),
    "fwd_jaxpr_eqns_scan":      Gate("higher", 0.10, 0.0),
    "fwd_jaxpr_eqn_growth":     Gate("higher", 0.0, 0.0),
    "kv_bytes_per_token_fp32":  Gate("higher", 0.0, 0.0),
    "kv_bytes_per_token_int8":  Gate("higher", 0.0, 0.0),
    "prefix_cache_hit_rate":    Gate("lower", 0.0, 0.10),
    "shared_page_fraction":     Gate("lower", 0.0, 0.10),
    # speculative decoding: launches per committed token must stay well
    # under 1 (disabling the draft drives it to exactly 1.0 — the
    # injected regression), acceptance must not collapse, and the spec
    # rounds must keep riding the ONE ragged executable
    "spec_target_steps_per_token": Gate("higher", 0.20, 0.02),
    "spec_accept_rate":         Gate("lower", 0.0, 0.15),
    "spec_decode_compiles":     Gate("higher", 0.0, 0.0),
    # GSPMD sharding: compile counts stay 1 under the mesh, the
    # collective mix of the tp=2 x dp=4 step is pinned exactly BOTH
    # ways (more collectives = partitioner drift; FEWER = the rule
    # table stopped matching and params silently replicated), and
    # per-device sharded KV bytes/token is exact accounting — forcing
    # the dp-only regime (--dp-only) doubles it and must fail the gate
    "gspmd_train_compiles":     Gate("higher", 0.0, 0.0),
    "gspmd_allreduce_count":    Gate("different"),
    "gspmd_allgather_count":    Gate("different"),
    "gspmd_serving_decode_compiles": Gate("higher", 0.0, 0.0),
    "gspmd_sharded_kv_bytes_per_token": Gate("higher", 0.0, 0.0),
    # cluster robustness (scripted kill-and-recover on the virtual
    # clock — every field is a deterministic count/fraction): fleet
    # goodput must not collapse (disabling retries via --no-retry
    # converts the killed replica's requeues into sheds and MUST fail
    # this gate), the requeue count is pinned exactly (a drift means
    # fault timing or routing changed — re-record deliberately), p99
    # TTFT gets modest slack, and unresolved requests are forbidden
    # outright (retry exhaustion must shed, never hang)
    "cluster_goodput_fraction": Gate("lower", 0.0, 0.05),
    "cluster_retries":          Gate("different"),
    "cluster_ttft_p99_s":       Gate("higher", 0.25, 0.02),
    "cluster_unresolved":       Gate("higher", 0.0, 0.0),
    # HLO fusion forensics (jit/hlo_forensics.py via probe_hlo_fusion):
    # fusion/kernel counts and bytes-touched-per-fused-region of the
    # jitted TrainStep and the ragged serving step are deterministic
    # for a pinned jaxlib, and MORE of any of them means a hot region
    # defused — silent 2x HBM traffic on chip. Exact one-sided pins:
    # an improvement (fewer kernels) passes, a regression fails.
    # --defuse (FLAGS_fusion_probe_barrier) is the injected regression
    # splitting the ragged layer's fused region; the serving gates must
    # catch it.
    "hlo_train_fusions":        Gate("higher", 0.0, 0.0),
    "hlo_train_kernels":        Gate("higher", 0.0, 0.0),
    "hlo_serving_fusions":      Gate("higher", 0.0, 0.0),
    "hlo_serving_kernels":      Gate("higher", 0.0, 0.0),
    "hlo_serving_fusion_bytes": Gate("higher", 0.0, 0.0),
    # request tracing (serving/tracing.py via probe_tracing): the
    # byte-identical-export contract is exact (0 = a wall-clock read or
    # hash-ordered container poisoned the span path), the span count is
    # pinned (schema/lifecycle-hook drift must be re-recorded
    # deliberately), and tracing must add zero step executables.
    "trace_deterministic":      Gate("lower", 0.0, 0.0),
    "trace_span_count":         Gate("different"),
    "trace_decode_compiles":    Gate("higher", 0.0, 0.0),
    # fleet telemetry (paddle_tpu.telemetry via probe_telemetry): the
    # full time-series/alert export must be byte-identical per seed,
    # the scrape count is pinned (cadence/run-length drift must be
    # re-recorded deliberately), the seeded slowdown fault must FIRE
    # and later RESOLVE the burn-rate alert (both pinned exactly —
    # --no-burn-alerts drops the rules, both read 0, and these gates
    # must catch it), and scraping must add zero step executables.
    "telemetry_deterministic":  Gate("lower", 0.0, 0.0),
    "telemetry_scrape_samples": Gate("different"),
    "telemetry_alerts_fired":   Gate("different"),
    "telemetry_alerts_resolved": Gate("different"),
    "telemetry_decode_compiles": Gate("higher", 0.0, 0.0),
    # crash-consistent persistence (io/persist.py via probe_persistence):
    # the killed-and-resumed loss trajectory must stay BIT-identical to
    # the unkilled run (0 = resume diverged or restored stale state),
    # restores must not fall back (a fallback means a stored version
    # failed verification), and the warm-restarted engine must serve
    # its pinned-prefix hit (0 = the store restored nothing and the
    # cohort prompt re-prefilled). --corrupt-checkpoint flips a byte in
    # every stored version: all three gates must catch it.
    "persist_resume_identical":  Gate("lower", 0.0, 0.0),
    "persist_restore_fallbacks": Gate("higher", 0.0, 0.0),
    "persist_warm_prefix_hits":  Gate("lower", 0.0, 0.0),
    # two-tier KV cache (serving/kv_tier.py via probe_kv_tiering): an
    # engine whose HBM page budget is strictly smaller than the seeded
    # workload's working set (long-context lane included) must serve it
    # TOKEN-IDENTICALLY to an all-HBM oracle, actually exercising the
    # tiers (spill/prefetch-hit counts pinned exactly — a drift means
    # the spill policy or admission math changed; re-record
    # deliberately), with ZERO steady-state prefetch stalls (every
    # restore staged a full round ahead of the cursor) and a
    # byte-reproducible loadgen report per seed. --no-prefetch disables
    # the cursor-ahead staging: every restore becomes a counted stall,
    # hits drop to 0, and these gates must catch it.
    "kv_tier_token_identical":   Gate("lower", 0.0, 0.0),
    "kv_tier_spills":            Gate("different"),
    "kv_tier_prefetch_hits":     Gate("different"),
    "kv_tier_stall_fraction":    Gate("higher", 0.0, 0.0),
    "kv_tier_deterministic":     Gate("lower", 0.0, 0.0),
    # disaggregated prefill/decode serving (serving/fabric.py via
    # probe_disagg): the disagg fleet must serve the seeded
    # shared-prefix workload (publisher crash included) token-
    # identically to a colocated fleet, actually move KV pages over
    # the fabric (the count is pinned exactly — a drift means the
    # handoff policy or router changed; re-record deliberately), hit
    # the fleet prefix cache cross-replica, keep transfer back-
    # pressure stalls at 0, reproduce the cluster report byte for
    # byte, and beat the colocated fleet's TTFT p99 on the long-prompt
    # flood (the ratio must stay well under 1). --colocated serves
    # both scenarios with roles=None: pages drop to 0, the hit rate
    # reads 0, the ratio collapses to ~1 — those three gates must all
    # catch it.
    "disagg_token_identical":    Gate("lower", 0.0, 0.0),
    "disagg_kv_pages_transferred": Gate("different"),
    "disagg_fleet_prefix_hit_rate": Gate("lower", 0.0, 0.0),
    "disagg_transfer_stall_fraction": Gate("higher", 0.0, 0.0),
    "disagg_ttft_ratio_vs_colocated": Gate("higher", 0.25, 0.05),
    "disagg_deterministic":      Gate("lower", 0.0, 0.0),
    # multi-tenant serving economy (paddle_tpu.tenancy via
    # probe_multitenant): the weighted-fair scheduler must hold the
    # good tenant's p99 TTFT flat while the metered noisy tenant
    # floods — the isolation ratio (good p99 / noisy p99, virtual
    # clock, deterministic) stays far below 1 and the abuser's
    # overflow is shed by quota (count pinned exactly per seed — a
    # drift means admission or refill math changed; re-record
    # deliberately). The tenant-annotated loadgen report must be
    # byte-reproducible, the mixed LoRA/base batch must decode the
    # base row bit-identically to a no-adapter engine over the int8
    # base, and adapter evict + hot-add must leave the ONE ragged
    # decode executable alone. --no-fairness serves the same flood
    # FIFO with no policy: sheds read 0, good's p99 blows out behind
    # the abuser's backlog, the isolation ratio collapses toward 1 —
    # the first three gates must all catch it.
    "multitenant_good_ttft_p99_s": Gate("higher", 0.25, 0.02),
    "multitenant_isolation_ratio": Gate("higher", 0.25, 0.05),
    "multitenant_quota_shed":    Gate("different"),
    "multitenant_deterministic": Gate("lower", 0.0, 0.0),
    "multitenant_mixed_batch_identical": Gate("lower", 0.0, 0.0),
    "multitenant_hot_swap_compiles": Gate("higher", 0.0, 0.0),
    # whole-model decode megakernel (kernels/decode_megakernel.py
    # fused_decode_model via probe_megakernel): the decoder layer body
    # must appear ONCE in the ragged step's program (launches/token
    # == 1.0 regardless of depth) and once in the burst executable
    # (1/burst_tokens per token), the engine must actually be at model
    # scope, tokens must stay bitwise identical to layer scope, and
    # the COMPILED ragged step's fusion/kernel counts are pinned
    # one-sided (the scanned prologue/epilogue chains appear once, not
    # once per layer). --per-layer forces the measured engine back to
    # layer scope: scope reads 0, launches/token rise to num_layers,
    # the compiled counts rise — five of the six gates must catch it.
    # pipeline-parallel stage axis (distributed/gspmd.py + the in-jit
    # 1F1B microbatch loop via probe_pipeline): pp=2 (and dp=2,pp=2)
    # training must stay loss-identical (<=1e-6) to the single-device
    # run — parity is a 0/1 verdict and 0 is an unconditional failure.
    # The stage-ring collective-permute count is structurally pinned
    # BOTH ways (5: forward shift + output collect + their two scan
    # transposes + the cotangent inject — more means the partitioner
    # started bouncing activations, fewer means the ring dissolved into
    # all-gathers), the max-stage param byte fraction must not rise
    # (a stage silently owning more than total/K + embed/head slack is
    # lost pipeline memory scaling), the analytic bubble fraction
    # (K-1)/(M+K-1) is cross-checked against the 1F1B schedule layout
    # inside the probe and pinned here, and the staged TrainStep must
    # still compile exactly once. --no-pipeline serves the same
    # microbatch count as pp=1 gradient accumulation: rings read 0,
    # the stage fraction reads 1.0, the bubble reads 0 — four gates
    # must catch it.
    "pipeline_loss_parity":      Gate("lower", 0.0, 0.0),
    "pipeline_ring_permutes":    Gate("different"),
    "pipeline_dp_ring_permutes": Gate("different"),
    "pipeline_max_stage_param_fraction": Gate("higher", 0.0, 0.0),
    "pipeline_bubble_fraction":  Gate("different"),
    "pipeline_train_compiles":   Gate("higher", 0.0, 0.0),
    "mk_model_scope":            Gate("lower", 0.0, 0.0),
    "mk_launches_per_token":     Gate("higher", 0.0, 0.0),
    "mk_burst_launches_per_token": Gate("higher", 0.0, 0.0),
    "mk_token_identity":         Gate("lower", 0.0, 0.0),
    "mk_serving_fusions":        Gate("higher", 0.0, 0.0),
    "mk_serving_kernels":        Gate("higher", 0.0, 0.0),
    # fused ragged prefill (kernels/prefill_megakernel.py via
    # probe_megakernel's mk_prefill_* family): the fused engine's
    # COMPILED ragged step is pinned one-sided strictly BELOW the
    # unfused mk_serving_* floor (the fused body drops the ragged
    # rank loops and fuses the projection chain — any rise is a
    # defusion), tokens must stay bitwise identical to the unfused
    # engine, launches-per-chunk must not rise (the ONE fixed-shape
    # step covers every chunk it packs), and the long-prompt-flood
    # TTFT under the launch-cost virtual-clock model must keep its
    # headline improvement (ratio vs unfused < 1; throughput must not
    # drop; decode progress pinned exactly — a flood that starves
    # decode is not a TTFT win). --per-layer-prefill builds the
    # measured engine UNFUSED: compiled counts climb to the floor,
    # the ratio reads 1.0, throughput drops — the gates must catch it.
    "mk_prefill_fusions":        Gate("higher", 0.0, 0.0),
    "mk_prefill_kernels":        Gate("higher", 0.0, 0.0),
    "mk_prefill_token_identity": Gate("lower", 0.0, 0.0),
    "mk_prefill_launches_per_chunk": Gate("higher", 0.0, 0.0),
    "mk_prefill_ttft_p99_s":     Gate("higher", 0.0, 0.0),
    "mk_prefill_ttft_ratio_vs_unfused": Gate("higher", 0.0, 0.0),
    "mk_prefill_tokens_per_s":   Gate("lower", 0.0, 0.0),
    "mk_prefill_decode_tokens":  Gate("different"),
}


def collect(probes=PROBES, burst_tokens=8, spec_tokens=4,
            gspmd_dp_only=False, cluster_retry_budget=2,
            fusion_defuse=False, telemetry_burn_alerts=True,
            persist_corrupt=False, kvtier_prefetch=True,
            disagg_colocated=False, multitenant_fairness=True,
            megakernel_per_layer=False, pipeline_no_pp=False,
            megakernel_per_layer_prefill=False) -> dict:
    """Run the selected probes; returns {backend, probes, metrics}.

    ``burst_tokens=1`` forces the serving engine's per-token dispatch
    path — the deliberate-regression hook the compare-mode test uses to
    prove the ``host_dispatches_per_token`` gate actually fires.
    ``spec_tokens=0`` disables the speculative draft the same way —
    target steps per committed token then reads exactly 1.0 and the
    ``spec_target_steps_per_token`` gate must catch it.
    ``gspmd_dp_only=True`` forces the data-parallel-only regime (no
    model axis) — per-device sharded KV bytes/token double and the
    ``gspmd_sharded_kv_bytes_per_token`` gate must catch it.
    ``cluster_retry_budget=0`` (--no-retry) disables cross-replica
    requeue in the kill-and-recover cluster probe — the killed
    replica's in-flight requests shed instead of retrying, fleet
    goodput collapses, and the ``cluster_goodput_fraction`` gate must
    catch it.
    ``fusion_defuse=True`` (--defuse) sets FLAGS_fusion_probe_barrier,
    splitting the ragged serving layer's hot fused region at trace time
    — fusion/kernel counts and fused-region bytes rise and the
    ``hlo_serving_*`` gates must catch it.
    ``telemetry_burn_alerts=False`` (--no-burn-alerts) drops the burn-
    rate rules from the telemetry probe's scraper — the seeded
    slowdown fault then fires (and resolves) nothing, both alert
    counts read 0, and the ``telemetry_alerts_*`` gates must catch it.
    ``persist_corrupt=True`` (--corrupt-checkpoint) flips a byte in
    every version of the probe's stored training checkpoint AND prefix
    store — resume identity breaks, restores fall back, warm hits
    vanish, and the ``persist_*`` gates must catch all of it.
    ``kvtier_prefetch=False`` (--no-prefetch) disables the two-tier KV
    probe's cursor-ahead staging — every parked-sequence restore
    becomes a counted stall and prefetch hits drop to 0; the
    ``kv_tier_stall_fraction`` and ``kv_tier_prefetch_hits`` gates
    must catch it.
    ``disagg_colocated=True`` (--colocated) serves the disagg probe's
    scenarios with ``roles=None`` — zero KV pages move over the
    fabric, the fleet prefix cache never hits, and the TTFT ratio
    collapses to ~1; the ``disagg_kv_pages_transferred``,
    ``disagg_fleet_prefix_hit_rate``, and
    ``disagg_ttft_ratio_vs_colocated`` gates must catch it.
    ``multitenant_fairness=False`` (--no-fairness) serves the
    multitenant probe's noisy-neighbor flood with NO tenant policy
    (bare FIFO): quota sheds read 0, the good tenant's p99 TTFT blows
    out behind the abuser's backlog, and the isolation ratio collapses
    toward 1; the ``multitenant_quota_shed``,
    ``multitenant_good_ttft_p99_s``, and
    ``multitenant_isolation_ratio`` gates must all catch it.
    ``pipeline_no_pp=True`` (--no-pipeline) replaces the pipeline-
    parallel probe's staged runs with pp=1 data-parallel runs at the
    SAME microbatch count (gradient accumulation): the pipeline ring
    permutes read 0, the max-stage param fraction reads 1.0 (no stage
    owns less than everything), and the bubble fraction reads 0 — the
    ``pipeline_ring_permutes``/``pipeline_dp_ring_permutes``/
    ``pipeline_max_stage_param_fraction``/``pipeline_bubble_fraction``
    gates must all catch it.
    ``megakernel_per_layer=True`` (--per-layer) forces the megakernel
    probe's measured engine back to layer scope: ``mk_model_scope``
    reads 0, launches per token rise from 1.0 to num_layers, the
    compiled ragged step's fusion/kernel counts rise — the
    ``mk_model_scope``/``mk_launches_per_token``/
    ``mk_burst_launches_per_token``/``mk_serving_*`` gates must all
    catch it.
    ``megakernel_per_layer_prefill=True`` (--per-layer-prefill) builds
    the fused-prefill measurement's engine UNFUSED: the compiled
    ragged-step counts climb back to the unfused ``mk_serving_*``
    floor, the flood TTFT ratio reads 1.0, and flood throughput drops
    — the ``mk_prefill_fusions``/``mk_prefill_kernels``/
    ``mk_prefill_ttft_p99_s``/``mk_prefill_ttft_ratio_vs_unfused``/
    ``mk_prefill_tokens_per_s`` gates must all catch it.
    """
    import jax
    import paddle_tpu as paddle
    from tools.bench_probes import (probe_cluster, probe_disagg,
                                    probe_gspmd,
                                    probe_hlo_fusion,
                                    probe_input_pipeline, probe_jaxpr,
                                    probe_pipeline,
                                    probe_kv_accounting,
                                    probe_megakernel,
                                    probe_multitenant,
                                    probe_opt_dispatches,
                                    probe_kv_tiering,
                                    probe_persistence, probe_serving,
                                    probe_spec_decode, probe_telemetry,
                                    probe_tracing)
    dev = jax.devices()[0]
    backend = dev.platform if dev.platform == "cpu" else \
        getattr(dev, "device_kind", "tpu").replace(" ", "-").lower()
    metrics: dict = {}
    errors: dict = {}

    def _take(blob, keys):
        for k in keys:
            metrics[k] = blob.get(k)
        for k, v in blob.items():
            if k.endswith("_probe_error"):
                errors[k] = v

    if "serving" in probes:
        _take(probe_serving(paddle, burst_tokens=burst_tokens),
              ("decode_compiles", "host_dispatches_per_token",
               "prefix_cache_hit_rate", "shared_page_fraction"))
    if "spec" in probes:
        _take(probe_spec_decode(paddle, spec_tokens=spec_tokens),
              ("spec_target_steps_per_token", "spec_accept_rate",
               "spec_decode_compiles"))
    if "gspmd" in probes:
        _take(probe_gspmd(paddle, dp_only=gspmd_dp_only),
              ("gspmd_train_compiles", "gspmd_allreduce_count",
               "gspmd_allgather_count", "gspmd_serving_decode_compiles",
               "gspmd_sharded_kv_bytes_per_token"))
    if "cluster" in probes:
        _take(probe_cluster(paddle, retry_budget=cluster_retry_budget),
              ("cluster_goodput_fraction", "cluster_retries",
               "cluster_ttft_p99_s", "cluster_unresolved"))
    if "optimizer" in probes:
        _take(probe_opt_dispatches(paddle), ("opt_dispatches_per_step",))
    if "input_pipeline" in probes:
        _take(probe_input_pipeline(paddle), ("host_syncs_per_epoch",))
    if "pipeline" in probes:
        _take(probe_pipeline(paddle, no_pipeline=pipeline_no_pp),
              ("pipeline_loss_parity", "pipeline_ring_permutes",
               "pipeline_dp_ring_permutes",
               "pipeline_max_stage_param_fraction",
               "pipeline_bubble_fraction", "pipeline_train_compiles"))
    if "jaxpr" in probes:
        _take(probe_jaxpr(paddle),
              ("fwd_jaxpr_eqns_scan", "fwd_jaxpr_eqn_growth"))
    if "accounting" in probes:
        _take(probe_kv_accounting(),
              ("kv_bytes_per_token_fp32", "kv_bytes_per_token_int8"))
    if "fusion" in probes:
        _take(probe_hlo_fusion(paddle, defuse=fusion_defuse),
              ("hlo_train_fusions", "hlo_train_kernels",
               "hlo_serving_fusions", "hlo_serving_kernels",
               "hlo_serving_fusion_bytes"))
    if "tracing" in probes:
        _take(probe_tracing(paddle),
              ("trace_deterministic", "trace_span_count",
               "trace_decode_compiles"))
    if "telemetry" in probes:
        _take(probe_telemetry(paddle, burn_alerts=telemetry_burn_alerts),
              ("telemetry_deterministic", "telemetry_scrape_samples",
               "telemetry_alerts_fired", "telemetry_alerts_resolved",
               "telemetry_decode_compiles"))
    if "persist" in probes:
        # the save/restore ms timings ride bench.py's artifact only —
        # wall-clock noise has no place in an exact-count gate set
        _take(probe_persistence(paddle, corrupt=persist_corrupt),
              ("persist_resume_identical", "persist_restore_fallbacks",
               "persist_warm_prefix_hits"))
    if "kvtier" in probes:
        # hbm/host page counts ride bench.py's artifact only — the
        # five gated fields are the deterministic contract
        _take(probe_kv_tiering(paddle, prefetch=kvtier_prefetch),
              ("kv_tier_token_identical", "kv_tier_spills",
               "kv_tier_prefetch_hits", "kv_tier_stall_fraction",
               "kv_tier_deterministic"))
    if "disagg" in probes:
        # the absolute TTFT p99s ride bench.py's artifact only — the
        # gated contract is the identity/pages/hit-rate/stall/ratio/
        # determinism sextet
        _take(probe_disagg(paddle, colocated=disagg_colocated),
              ("disagg_token_identical", "disagg_kv_pages_transferred",
               "disagg_fleet_prefix_hit_rate",
               "disagg_transfer_stall_fraction",
               "disagg_ttft_ratio_vs_colocated",
               "disagg_deterministic"))
    if "multitenant" in probes:
        _take(probe_multitenant(paddle, fairness=multitenant_fairness),
              ("multitenant_good_ttft_p99_s",
               "multitenant_isolation_ratio", "multitenant_quota_shed",
               "multitenant_deterministic",
               "multitenant_mixed_batch_identical",
               "multitenant_hot_swap_compiles"))
    if "megakernel" in probes:
        _take(probe_megakernel(
                  paddle, per_layer=megakernel_per_layer,
                  per_layer_prefill=megakernel_per_layer_prefill),
              ("mk_model_scope", "mk_launches_per_token",
               "mk_burst_launches_per_token", "mk_token_identity",
               "mk_serving_fusions", "mk_serving_kernels",
               "mk_prefill_fusions", "mk_prefill_kernels",
               "mk_prefill_token_identity",
               "mk_prefill_launches_per_chunk",
               "mk_prefill_ttft_p99_s",
               "mk_prefill_ttft_ratio_vs_unfused",
               "mk_prefill_tokens_per_s", "mk_prefill_decode_tokens"))
    out = {"backend": backend, "probes": sorted(probes),
           "metrics": metrics}
    if errors:
        out["probe_errors"] = errors
    return out


def gate(current, baseline, *, require_all=True):
    """Compare a collection against a baseline blob of the same backend.

    Returns (failures, report_str): failures is [(metric, reason)].
    ``require_all=False`` skips baseline metrics absent from the current
    run (partial --probes collections); full runs treat a missing metric
    as a failure — silent coverage loss must not read as a pass.
    """
    failures, lines = [], []
    base = baseline.get("metrics", {})
    for name, cur in sorted(current.get("metrics", {}).items()):
        ref = base.get(name)
        g = GATES.get(name, Gate("higher", 0.25, 0.0))
        if ref is None:
            lines.append(f"  {name:<28} {cur!s:>12}   (new, no baseline)")
            continue
        if cur is None:
            lines.append(f"  {name:<28} {'null':>12}   baseline "
                         f"{ref:>10}   << PROBE BROKE")
            failures.append((name, "measurement is null"))
            continue
        bad = g.bad(cur, ref)
        flag = "  << REGRESSION" if bad else ""
        op = {"higher": ">", "lower": "<", "different": "!="}[g.worse]
        lines.append(
            f"  {name:<28} {cur:>12.4f}   baseline {ref:>10.4f}   "
            f"(fail {op} {g.bound(ref):.4f}){flag}")
        if bad:
            failures.append(
                (name, f"{cur} vs baseline {ref} "
                       f"(worse={g.worse}, bound {g.bound(ref):.4f})"))
    missing = sorted(set(base) - set(current.get("metrics", {})))
    if require_all:
        for name in missing:
            lines.append(f"  {name:<28} MISSING from current run")
            failures.append((name, "missing from current run"))
    return failures, "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="CPU-tier proxy perf bench (counts, not timings)")
    ap.add_argument("--record", action="store_true",
                    help="write the baseline for this backend")
    ap.add_argument("--compare", metavar="BASELINE", nargs="?",
                    const=BASELINE_PATH, default=None,
                    help="gate against a baseline file (default: "
                         "tools/proxy_bench_baseline.json)")
    ap.add_argument("--json", action="store_true",
                    help="print the collection JSON only")
    ap.add_argument("--probes", default=",".join(PROBES),
                    help=f"comma list from {PROBES}")
    ap.add_argument("--burst-tokens", type=int, default=8,
                    help="serving probe burst length (1 forces the "
                         "per-token dispatch path)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="spec probe draft length (0 disables the draft "
                         "— one target launch per token again)")
    ap.add_argument("--dp-only", action="store_true",
                    help="force the gspmd probe's data-parallel-only "
                         "regime (no model axis — per-device sharded KV "
                         "bytes/token double; the injected regression)")
    ap.add_argument("--no-retry", action="store_true",
                    help="zero the cluster probe's retry budget: the "
                         "killed replica's requests shed instead of "
                         "requeueing, fleet goodput collapses (the "
                         "injected regression)")
    ap.add_argument("--defuse", action="store_true",
                    help="set FLAGS_fusion_probe_barrier in the fusion "
                         "probe: an optimization barrier splits the "
                         "ragged layer's hot fused region, fusion/"
                         "kernel counts rise (the injected regression)")
    ap.add_argument("--no-burn-alerts", action="store_true",
                    help="drop the burn-rate rules from the telemetry "
                         "probe's scraper: the seeded slowdown fault "
                         "fires no alert, fired/resolved counts read 0 "
                         "(the injected regression)")
    ap.add_argument("--corrupt-checkpoint", action="store_true",
                    help="flip a byte in every version of the "
                         "persistence probe's stored checkpoint and "
                         "prefix store: resume identity breaks and "
                         "warm prefix hits vanish (the injected "
                         "regression)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the two-tier KV probe's cursor-ahead "
                         "staging: every parked-sequence restore "
                         "becomes a counted stall and prefetch hits "
                         "read 0 (the injected regression)")
    ap.add_argument("--colocated", action="store_true",
                    help="serve the disagg probe's scenarios with "
                         "roles=None: zero pages move over the fabric, "
                         "the fleet prefix cache never hits, and the "
                         "TTFT ratio collapses to ~1 (the injected "
                         "regression)")
    ap.add_argument("--per-layer", action="store_true",
                    help="force the megakernel probe's measured engine "
                         "back to layer scope: launches per token rise "
                         "from 1.0 to num_layers and the compiled "
                         "fusion/kernel counts rise (the injected "
                         "regression)")
    ap.add_argument("--per-layer-prefill", action="store_true",
                    help="build the fused-prefill measurement's engine "
                         "UNFUSED: the compiled ragged-step counts "
                         "climb back to the unfused floor and the "
                         "flood TTFT ratio reads 1.0 (the injected "
                         "regression)")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="replace the pipeline probe's staged runs "
                         "with pp=1 gradient accumulation at the same "
                         "microbatch count: ring permutes read 0, the "
                         "max-stage fraction reads 1.0, the bubble "
                         "reads 0 (the injected regression)")
    ap.add_argument("--no-fairness", action="store_true",
                    help="serve the multitenant probe's noisy-neighbor "
                         "flood with no tenant policy (bare FIFO): "
                         "quota sheds read 0 and the good tenant's p99 "
                         "TTFT blows out (the injected regression)")
    args = ap.parse_args(argv)

    probes = tuple(p for p in args.probes.split(",") if p)
    unknown = set(probes) - set(PROBES)
    if unknown:
        print(f"unknown probes: {sorted(unknown)}", file=sys.stderr)
        return 2
    if args.record and args.compare is not None:
        # record-then-compare-against-itself would always pass; an
        # operator asking for both almost certainly wants a real gate
        # first — make them choose
        print("--record and --compare are mutually exclusive",
              file=sys.stderr)
        return 2
    if args.record and set(probes) != set(PROBES):
        # a partial recording would overwrite the backend's baseline
        # with a subset and every later full compare would read the
        # dropped metrics as "(new, no baseline)" — silent coverage loss
        print("--record requires the full probe set (a partial "
              "recording would shrink gate coverage)", file=sys.stderr)
        return 2
    current = collect(probes=probes, burst_tokens=args.burst_tokens,
                      spec_tokens=args.spec_tokens,
                      gspmd_dp_only=args.dp_only,
                      cluster_retry_budget=0 if args.no_retry else 2,
                      fusion_defuse=args.defuse,
                      telemetry_burn_alerts=not args.no_burn_alerts,
                      persist_corrupt=args.corrupt_checkpoint,
                      kvtier_prefetch=not args.no_prefetch,
                      disagg_colocated=args.colocated,
                      multitenant_fairness=not args.no_fairness,
                      megakernel_per_layer=args.per_layer,
                      pipeline_no_pp=args.no_pipeline,
                      megakernel_per_layer_prefill=args.per_layer_prefill)

    if args.json:
        # --json changes the output format, never the action: combined
        # with --compare (or --record) the gate/recording still runs
        # and still sets the exit code
        print(json.dumps(current, indent=1, sort_keys=True))
        if args.compare is None and not args.record:
            return 0
    elif not args.record and args.compare is None:
        print(json.dumps(current, indent=1, sort_keys=True))
        return 0

    if args.record:
        # a baseline with a null metric (or a probe that errored) would
        # make gate() read that metric as "(new, no baseline)" forever —
        # coverage silently lost on the RECORDING side of the compare
        nulls = sorted(k for k, v in current["metrics"].items()
                       if v is None)
        if nulls or current.get("probe_errors"):
            print(f"refusing to record a broken collection: null "
                  f"metrics {nulls}, probe errors "
                  f"{current.get('probe_errors')}", file=sys.stderr)
            return 2
        baselines = {}
        if os.path.exists(BASELINE_PATH):
            with open(BASELINE_PATH) as f:
                baselines = json.load(f)
        baselines[current["backend"]] = current
        with open(BASELINE_PATH, "w") as f:
            json.dump(baselines, f, indent=1, sort_keys=True)
            f.write("\n")
        # status goes to stderr under --json: stdout stays pure JSON
        print(f"recorded baseline for backend={current['backend']} "
              f"({len(current['metrics'])} metrics) -> {BASELINE_PATH}",
              file=sys.stderr if args.json else sys.stdout)
        return 0

    try:
        with open(args.compare) as f:
            baselines = json.load(f)
    except (OSError, ValueError) as e:
        print(f"cannot read baseline {args.compare}: {e}", file=sys.stderr)
        return 2
    baseline = baselines.get(current["backend"])
    if baseline is None:
        print(f"no baseline for backend={current['backend']} in "
              f"{args.compare}; run `python -m tools.proxy_bench "
              f"--record` first", file=sys.stderr)
        return 2
    failures, report = gate(current, baseline,
                            require_all=set(probes) == set(PROBES))
    # with --json, stdout is the collection JSON and nothing else (it
    # must stay machine-parseable); the human report moves to stderr
    dst = sys.stderr if args.json else sys.stdout
    print(f"proxy bench gate  backend={current['backend']} "
          f"probes={','.join(sorted(probes))}", file=dst)
    print(report, file=dst)
    if current.get("probe_errors"):
        print(f"probe errors: {current['probe_errors']}", file=sys.stderr)
    if failures:
        print("FAIL: " + "; ".join(f"{n}: {r}" for n, r in failures),
              file=sys.stderr)
        return 1
    print("PASS", file=dst)
    return 0


if __name__ == "__main__":
    sys.exit(main())
