"""Serving metrics: counters/gauges/histograms for the engine, scheduler,
and pool.

Two consumers:
- ``snapshot()`` — a plain dict for tests and operators polling the
  engine (``LLMEngine.metrics_snapshot()`` adds the engine's own fields;
  the telemetry ``Scraper`` samples both);
- the profiler timeline — each ``record_step`` emits instant events
  through the same native recorder paddle_tpu.profiler drains, so serving
  gauges land on the chrome-trace/protobuf timeline next to op spans when
  a Profiler is recording.

Latency observability (the loadgen substrate, docs/SERVING.md
"Workload schema"): every
FINISHED request records its TTFT (arrival -> first generated token),
TPOT (mean inter-token time after the first) and e2e latency into
bounded-reservoir :class:`Histogram`\\ s, so p50/p90/p99 exist on any
long-running engine without an external harness. Queue starvation is
observable through the ``queue_age_p99_s`` / ``max_queue_wait_s`` gauges
(per-request enqueue timestamps come from the scheduler's ``now_fn``, so
they are virtual-clock-accurate under paddle_tpu.loadgen).
"""
from __future__ import annotations

import random
import time
import zlib
from collections import deque

from ..core import native as _nv


def percentile_of(values, q):
    """Deterministic linear-interpolation percentile of a value list
    (numpy's default method, dependency-free). None on empty input."""
    if not values:
        return None
    s = sorted(float(v) for v in values)
    n = len(s)
    if n == 1:
        return s[0]
    pos = (q / 100.0) * (n - 1)
    lo = int(pos)
    if lo >= n - 1:
        return s[-1]
    frac = pos - lo
    return s[lo] + (s[lo + 1] - s[lo]) * frac


class Counter:
    __slots__ = ("name", "value")

    def __init__(self, name):
        self.name = name
        self.value = 0

    def inc(self, n=1):
        self.value += n


class Gauge:
    """Point-in-time value, stamped with its last-update time.

    ``updated_at`` (the caller's ``now_fn`` time base — the engine's
    virtual clock under loadgen) is what separates "this replica's queue
    is empty" from "this replica stopped reporting": a gauge that was
    last set before a replica died keeps its final value forever, and
    without the stamp a fleet health read cannot tell. ``age_s(now)``
    is None until the first ``set`` — a never-set gauge has no age, it
    has no data."""

    __slots__ = ("name", "value", "updated_at", "_now")

    def __init__(self, name, now_fn=None):
        self.name = name
        self.value = 0.0
        #: time of the last set() on the owner's now_fn clock; None
        #: until the gauge is first written
        self.updated_at = None
        self._now = now_fn

    def set(self, v):
        self.value = v
        if self._now is not None:
            self.updated_at = self._now()

    def age_s(self, now) -> float | None:
        """Seconds since the last set (None if never set) — the
        staleness signal snapshots and the telemetry scraper key off."""
        return None if self.updated_at is None else now - self.updated_at


class Histogram:
    """Bounded-reservoir histogram with percentile queries.

    Memory is capped at ``max_samples`` observations (classic reservoir
    sampling beyond that), so a long-running server's latency histograms
    never grow with traffic; below the cap the percentiles are exact.
    The reservoir's replacement stream is seeded from the histogram's
    NAME (crc32 — stable across processes, unlike ``hash``), so two runs
    observing identical value streams report bit-identical percentiles —
    the loadgen determinism gate (tests/test_loadgen.py) depends on it.
    """

    __slots__ = ("name", "count", "total", "min", "max", "max_samples",
                 "_samples", "_rng")

    def __init__(self, name, max_samples=2048):
        if max_samples < 1:
            raise ValueError("max_samples must be >= 1")
        self.name = name
        self.max_samples = int(max_samples)
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self._samples = []
        self._rng = random.Random(zlib.crc32(str(name).encode("utf-8")))

    def observe(self, v):
        v = float(v)
        self.count += 1
        self.total += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)
        if len(self._samples) < self.max_samples:
            self._samples.append(v)
        else:
            j = self._rng.randrange(self.count)
            if j < self.max_samples:
                self._samples[j] = v

    @property
    def mean(self):
        return self.total / self.count if self.count else None

    def percentile(self, q):
        """q in [0, 100]; None when nothing was observed — an empty
        reservoir has no percentiles, never a fabricated 0
        (tests/test_telemetry.py pins the contract, merge included)."""
        return percentile_of(self._samples, q)

    def summary(self) -> dict:
        """{count, mean, min, max, p50, p90, p99} — Nones when empty."""
        return {"count": self.count, "mean": self.mean,
                "min": self.min, "max": self.max,
                "p50": self.percentile(50), "p90": self.percentile(90),
                "p99": self.percentile(99)}

    def sample_state(self) -> dict:
        """Plain-data copy of the histogram's observable state —
        what the telemetry scraper retains per replica so a crashed
        engine's latency population survives into fleet percentiles
        (the counter-carry discipline, histogram edition)."""
        return {"count": self.count, "total": self.total,
                "min": self.min, "max": self.max,
                "samples": list(self._samples)}

    @classmethod
    def merge(cls, sources, *, name="merged", max_samples=None):
        """Deterministically merge histograms (or ``sample_state()``
        dicts) into one — the fleet-percentile primitive: each
        replica's bounded reservoir contributes its retained samples IN
        CALLER ORDER through the merged histogram's own crc32-name-
        seeded reservoir, so two merges of the same sources are
        bit-identical; count/total/min/max are then corrected to the
        TRUE aggregates (they never sample). Below every reservoir's
        cap the merged percentiles are exact over the pooled
        population; above it they are reservoir-approximate, like any
        single histogram's. Empty sources merge to an empty histogram
        whose percentiles are None — never a fabricated 0."""
        if max_samples is None:
            caps = [s.max_samples for s in sources
                    if isinstance(s, Histogram)]
            max_samples = max(caps) if caps else 2048
        out = cls(name, max_samples=max_samples)
        count = 0
        total = 0.0
        mn = mx = None
        for src in sources:
            st = src.sample_state() if isinstance(src, Histogram) else src
            for v in st["samples"]:
                out.observe(v)
            count += st["count"]
            total += st["total"]
            if st["min"] is not None:
                mn = st["min"] if mn is None else min(mn, st["min"])
                mx = st["max"] if mx is None else max(mx, st["max"])
        # observe() tracked the RETAINED samples; the aggregate stats
        # must reflect every observation the sources ever made
        out.count = count
        out.total = total
        out.min = mn
        out.max = mx
        return out


class ServingMetrics:
    COUNTERS = ("requests_added", "rejected_requests", "tokens_generated",
                "prefills", "prefill_chunks", "decode_steps", "preemptions",
                "shed_requests", "cancelled_requests", "finished_requests",
                "decode_compiles", "cow_copies", "prefix_cache_hits",
                "prefix_cache_misses",
                # burst/megakernel forensics: jitted launches the host
                # issued (the dispatch gate's numerator), on-device
                # generation bursts, and prefix-cache hits served by a
                # PINNED chain after its last sequence sharer left
                "host_dispatches", "burst_launches", "pinned_prefix_hits",
                # host<->device transfers the ragged launch made
                # (_launch: one put of the control buffer and one
                # read-back an ordinary round, so 2 x its dispatches; the
                # draft's candidates one more a speculative round; the
                # burst loop's own puts are not counted)
                "host_transfers",
                # fused ragged prefill (kernels/prefill_megakernel.py):
                # steps that served >= 1 prefill-chunk row — the ragged
                # step is ONE executable, so each such step is ONE
                # launch covering every chunk in it;
                # prefill_launches / prefill_chunks is the
                # launches-per-chunk headline the fused path collapses
                "prefill_launches",
                # speculative decoding (serving/spec_decode.py): draft
                # candidates offered for verification, candidates the
                # rejection sampler accepted, verification rounds that
                # rolled a KV tail back (>= 1 candidate rejected), and
                # spec rounds run
                "spec_drafted_tokens", "spec_accepted_tokens",
                "spec_rollbacks", "spec_rounds",
                # rounds demoted to ordinary decode because the DRAFT
                # pool could not hold them (under-sized draft_num_pages)
                "spec_draft_fallbacks",
                # robustness (PR 11): running/waiting requests aborted at
                # a step boundary because their e2e deadline passed
                # (finish_reason "deadline_exceeded"), ragged rows whose
                # logits came back NaN/Inf (the in-graph isfinite guard —
                # each aborts its request instead of sampling garbage),
                # and graceful-degradation ladder transitions (rungs
                # engaged under sustained pressure / restored after it
                # clears — serving/cluster.DegradationLadder)
                "deadline_aborts", "nonfinite_rows",
                "degradation_escalations", "degradation_restorations",
                # observability (PR 12): flight-recorder post-mortem
                # dumps taken (InvariantViolation / nonfinite abort /
                # replica crash auto-dumps + any operator-requested one)
                "flight_dumps",
                # crash-consistent persistence (io/persist.py): degraded
                # restores — a corrupt/unusable persisted artifact fell
                # back to an older version or to a cold start instead of
                # loading bad bytes; pinned prefix chains warm-reloaded
                # from the store at engine construction; pin-set
                # snapshots persisted (the write-ahead warm-start path)
                "restore_fallbacks", "prefix_chains_restored",
                "prefix_store_saves",
                # two-tier KV cache (serving/kv_tier.py): pages spilled
                # to the host-RAM arena (cold pages of parked
                # sequences), parked-sequence restores served from a
                # cursor-ahead background staging, and restores the
                # prefetcher did NOT stage a full round ahead — the
                # counted, bounded stall (the copy runs synchronously;
                # tokens stay bit-identical, only overlap is lost)
                "kv_spills", "kv_prefetch_hits", "kv_prefetch_stalls",
                # disaggregated serving (serving/fabric.py): KV pages
                # landed on THIS replica over the fabric (decode side of
                # a prefill -> decode handoff), handoffs the bounded
                # fabric refused this round (issue retried next round —
                # the counted backpressure signal), and prefix-cache
                # hits served from the FLEET store (pages prefilled on
                # another replica, faulted in content-addressed)
                "kv_pages_transferred", "transfer_stalls",
                "fleet_prefix_hits",
                # multi-tenant economy (paddle_tpu.tenancy): waiting
                # requests shed because their tenant's token bucket
                # could not fund them (reason "quota_exceeded"), LoRA
                # adapters hot-published into the registry, slots
                # reclaimed by LRU eviction, evictions REFUSED because
                # in-flight requests still wear the adapter (the
                # structured AdapterInUse path — never a silent slot-0
                # fallback), adapters warm-reloaded from the store at
                # engine construction, and adapter-store snapshots
                # persisted
                "quota_shed_requests", "adapter_hot_adds",
                "adapter_evictions", "adapter_evict_refusals",
                "adapter_restores", "adapter_store_saves")
    GAUGES = ("queue_depth", "running_seqs", "waiting_seqs",
              "page_utilization", "tokens_per_s", "ragged_pad_fraction",
              "shared_page_fraction", "pinned_pages",
              # lifetime draft acceptance rate (accepted / drafted) —
              # the headline spec-decoding health signal: target steps
              # per committed token ~= 1 / (1 + accept_rate * k)
              "spec_accept_rate",
              # starvation observability: age of the oldest / p99 waiting
              # request (seconds since it was (re-)enqueued, scheduler
              # now_fn time base) — a climbing max_queue_wait_s under
              # steady load is head-of-line blocking made visible
              "queue_age_p99_s", "max_queue_wait_s",
              # current graceful-degradation rung (0 = full service;
              # each rung sheds one optional capability in order)
              "degradation_level",
              # two-tier KV cache: host-arena slots in use (sequences +
              # host-tier pinned chains) and the fraction of live KV
              # pages that are HBM-resident (1.0 for single-tier pools
              # — there is no second tier to be non-resident in)
              "kv_host_pages_used", "kv_resident_fraction",
              # multi-tenant LoRA: adapter registry slots in use (slot 0
              # — the base model — never counts); 0 for engines without
              # a registry
              "adapter_slots_used")
    #: per-finished-request latency distributions (seconds): TTFT =
    #: arrival -> first generated token, TPOT = mean inter-token after
    #: the first, e2e = arrival -> finalization
    HISTOGRAMS = ("ttft_s", "tpot_s", "e2e_s")

    #: tokens_per_s is the rate over this trailing window, not a lifetime
    #: average — a lifetime average decays toward zero across idle gaps
    RATE_WINDOW_S = 60.0

    def __init__(self, now_fn=time.monotonic, *, stale_after_s=None):
        self._now = now_fn
        self._t0 = now_fn()
        #: gauge-staleness horizon: a gauge last set more than this many
        #: seconds ago (or never set) is MARKED in snapshot() — its
        #: value is reported as null and its name listed under
        #: ``stale_gauges`` — instead of silently reading as current.
        #: None (the default) disables marking; the telemetry scraper
        #: applies its own horizon either way.
        self.stale_after_s = stale_after_s
        self._rate_samples = deque([(self._t0, 0)])   # (t, tokens_total)
        for c in self.COUNTERS:
            setattr(self, c, Counter(c))
        for g in self.GAUGES:
            setattr(self, g, Gauge(g, now_fn=now_fn))
        for h in self.HISTOGRAMS:
            setattr(self, h, Histogram(h))

    def record_request_end(self, *, arrival, first_token_at, finished_at,
                           n_tokens):
        """Observe one FINISHED request's latencies into the histograms.
        Called by the engine at finalization; shed/cancelled/aborted
        requests never get here (their "latency" is not a service time).
        """
        self.e2e_s.observe(finished_at - arrival)
        if first_token_at is not None:
            self.ttft_s.observe(first_token_at - arrival)
            if n_tokens > 1:
                self.tpot_s.observe(
                    (finished_at - first_token_at) / (n_tokens - 1))

    def record_step(self, scheduler, pool):
        """Refresh gauges from live state; emit profiler instants."""
        self.queue_depth.set(scheduler.queue_depth())
        self.running_seqs.set(len(scheduler.running))
        self.waiting_seqs.set(len(scheduler.waiting))
        self.page_utilization.set(pool.utilization)
        self.shared_page_fraction.set(
            getattr(pool, "shared_page_fraction", 0.0))
        self.pinned_pages.set(getattr(pool, "pinned_pages", 0))
        # two-tier KV sync (kv_tier.py): the pool owns the lifetime
        # tier-traffic integers; fold the deltas into the counters so
        # the cluster's counter-carry and the telemetry scraper's
        # delta decoding see ordinary monotonic counters
        spills = getattr(pool, "spills", None)
        if spills is not None:
            self.kv_spills.inc(spills - self.kv_spills.value)
            self.kv_prefetch_hits.inc(
                pool.prefetch_hits - self.kv_prefetch_hits.value)
            self.kv_prefetch_stalls.inc(
                pool.prefetch_stalls - self.kv_prefetch_stalls.value)
            self.kv_host_pages_used.set(pool.host_pages_used)
            self.kv_resident_fraction.set(pool.resident_fraction)
        else:
            self.kv_host_pages_used.set(0.0)
            self.kv_resident_fraction.set(1.0)
        now = self._now()
        ages = scheduler.queue_ages(now) \
            if hasattr(scheduler, "queue_ages") else []
        self.max_queue_wait_s.set(max(ages) if ages else 0.0)
        self.queue_age_p99_s.set(percentile_of(ages, 99) or 0.0)
        self._rate_samples.append((now, self.tokens_generated.value))
        while len(self._rate_samples) > 2 and \
                now - self._rate_samples[0][0] > self.RATE_WINDOW_S:
            self._rate_samples.popleft()
        t_old, tok_old = self._rate_samples[0]
        self.tokens_per_s.set(
            (self.tokens_generated.value - tok_old) / max(now - t_old, 1e-9))
        if _nv.prof_enabled():
            for g in self.GAUGES:
                v = getattr(self, g).value
                _nv.prof_instant(f"serving.{g}={v:.3f}", 3)

    def snapshot(self) -> dict:
        out = {c: getattr(self, c).value for c in self.COUNTERS}
        now = self._now()
        stale = []
        for g in self.GAUGES:
            gauge = getattr(self, g)
            age = gauge.age_s(now)
            if self.stale_after_s is not None and \
                    (age is None or age > self.stale_after_s):
                # a stale gauge reads as null, never as its last value:
                # "the queue was empty when this replica last reported"
                # must not masquerade as "the queue is empty now"
                out[g] = None
                stale.append(g)
            else:
                out[g] = gauge.value
        out["stale_gauges"] = stale
        for h in self.HISTOGRAMS:
            hist = getattr(self, h)
            out[f"{h}_count"] = hist.count
            for q in (50, 90, 99):
                out[f"{h}_p{q}"] = hist.percentile(q)
        out["uptime_s"] = now - self._t0
        return out


__all__ = ["Counter", "Gauge", "Histogram", "ServingMetrics",
           "percentile_of"]
