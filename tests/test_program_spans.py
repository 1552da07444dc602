"""The always-on spans inside ``LLMEngine.step`` and ``TrainStep.__call__``
(ISSUE 26): one primitive (``paddle_tpu.profiler.spans``), every nested
span on the profiler's timeline AND in one bounded process-wide log, the
counts of what a step carried taken where the work happens.

CPU, tiny Llama. The log is shared by the process, so every test reads
the records made after a mark it takes itself (ids only grow)."""
import glob
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.profiler import spans
from paddle_tpu.profiler.spans import span
from paddle_tpu.serving import LLMEngine

PHASES = ("serve.plan", "serve.assemble", "serve.dispatch", "serve.wait",
          "serve.commit")


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=64,
                            intermediate_size=128, num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _engine(model, **kw):
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("seed", 0)
    kw.setdefault("chunk_size", 8)
    kw.setdefault("now_fn", time.perf_counter)
    return LLMEngine(model, **kw)


def _mark():
    with span("test.mark") as m:
        pass
    return m.id


def _since(mark, name=None):
    return [r for r in spans.records(name) if r.id > mark]


def _prompts(n, lo=5, hi=30, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_records_nesting_attrs_and_self_time():
    mark = _mark()
    with span("t.outer", a=1) as outer:
        with span("t.inner") as inner:
            inner.set(rows=3)
        outer.set(b="x")
    inner_r, outer_r = _since(mark)
    assert (inner_r.name, outer_r.name) == ("t.inner", "t.outer")
    assert inner_r.parent_id == outer_r.id and outer_r.parent_id == 0
    assert outer_r.t0_ns <= inner_r.t0_ns <= inner_r.t1_ns <= outer_r.t1_ns
    assert inner_r.attrs == {"rows": 3}
    assert outer_r.attrs == {"a": 1, "b": "x"}
    s = spans.summary(prefix="t.", last=2)
    assert s["t.inner"]["count"] == 1
    want_self = (outer_r.t1_ns - outer_r.t0_ns
                 - (inner_r.t1_ns - inner_r.t0_ns)) / 1e6
    assert s["t.outer"]["self_ms"] == pytest.approx(want_self)
    assert s["t.outer"]["total_ms"] >= s["t.outer"]["self_ms"]


def test_phases_follow_one_another_and_survive_an_exception():
    mark = _mark()
    with pytest.raises(RuntimeError):
        with span("t.step") as sp:
            sp.phase("t.plan")
            sp.phase("t.launch")
            sp.set(rows=2)
            raise RuntimeError("mid-phase")
    plan, launch, step = _since(mark)
    assert [r.name for r in (plan, launch, step)] == \
        ["t.plan", "t.launch", "t.step"]
    assert plan.parent_id == launch.parent_id == step.id
    assert plan.t1_ns <= launch.t0_ns and launch.t1_ns <= step.t1_ns
    assert plan.attrs is None and step.attrs == {"rows": 2}
    # the thread's stack is clean again: the next span has no parent
    with span("t.after"):
        pass
    assert _since(mark, "t.after")[0].parent_id == 0


def test_detached_spans_overlap_and_are_listed_while_open():
    mark = _mark()
    a = spans.begin("t.life", request="a")
    b = spans.begin("t.life", request="b")
    open_now = {r.attrs["request"]: r for r in spans.open_spans("t.life")}
    assert set(open_now) >= {"a", "b"}
    assert open_now["a"].t1_ns is None and open_now["a"].t0_ns == a.t0_ns
    a.set(chunks=1)
    a.end()
    a.end()                                   # a second end does nothing
    b.end()
    ra, rb = _since(mark, "t.life")
    assert ra.attrs == {"request": "a", "chunks": 1}
    assert ra.parent_id == rb.parent_id == 0
    assert rb.t0_ns < ra.t1_ns                # they overlapped
    assert not [r for r in spans.open_spans("t.life")
                if r.attrs["request"] in ("a", "b")]


def test_a_dropped_owner_takes_its_open_spans_along():
    life = spans.begin("t.orphan", request="gone")
    assert spans.open_spans("t.orphan")
    del life
    assert not spans.open_spans("t.orphan")


def test_the_log_is_bounded():
    """(e) appending past the capacity keeps the length."""
    try:
        for _ in range(spans.LOG_CAPACITY + 10):
            with span("t.fill"):
                pass
        assert len(spans.records()) == spans.LOG_CAPACITY
        with span("t.newest"):
            pass
        recs = spans.records()
        assert len(recs) == spans.LOG_CAPACITY
        assert recs[-1].name == "t.newest"
    finally:
        spans.clear()       # 2**17 records would slow every later reader


def test_summary_reads_while_another_thread_writes():
    """A scrape thread takes ``metrics_snapshot()`` while the engine
    steps: the summary copies the log in one step, so an append from
    the other thread cannot break its walk."""
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            with span("t.busy"):
                pass

    t = threading.Thread(target=writer)
    t.start()
    try:
        for _ in range(1000):
            spans.summary(prefix="t.", last=4096)
    finally:
        stop.set()
        t.join()
        spans.clear()


def test_summary_keeps_one_tag_and_its_children():
    mark = _mark()
    for engine in (0, 1, 0):
        with span("t.step", engine=engine) as sp:
            sp.phase("t.plan")
    spans.begin("t.life", request="r", engine=1).end()
    n = len(_since(mark))
    both = spans.summary(prefix="t.", last=n)
    assert both["t.step"]["count"] == both["t.plan"]["count"] == 3
    one = spans.summary(prefix="t.", last=n, engine=1)
    assert {k: v["count"] for k, v in one.items()} == \
        {"t.step": 1, "t.plan": 1, "t.life": 1}
    assert spans.summary(prefix="t.", last=n, engine=0)["t.plan"]["count"] \
        == 2


# ---------------------------------------------------------------------------
# the serving step
# ---------------------------------------------------------------------------

def _run_counting(eng, prompts, max_new=4):
    """Drive the engine to the end; returns the steps made and, a step,
    the context attention had to read, the query tokens scheduled and
    what the ragged kernel's walk covers, as the scheduler's plan gives
    them."""
    from paddle_tpu.kernels.paged_attention import (
        ragged_kv_tokens_read, ragged_kv_walks, ragged_q_tile)
    want_live = []
    prepare = eng.scheduler.prepare_step
    cfg = eng.cfg
    tile = ragged_q_tile(
        eng.q_block, cfg.num_attention_heads // cfg.num_key_value_heads,
        eng.step_token_budget)

    def spy():
        plan = prepare()
        if plan is not None:
            q_lens = [q_len for _, _, q_len in plan.rows]
            kv_lens = [seq.cached_len + q_len for seq, _, q_len in plan.rows]
            want_live.append((sum(kv_lens), sum(q_lens),
                              ragged_kv_tokens_read(
                                  q_lens, kv_lens, q_block=eng.q_block,
                                  q_tile=tile, page_size=eng.page_size,
                                  pages_per_seq=eng.max_pages_per_seq),
                              ragged_kv_walks(q_lens, q_block=eng.q_block,
                                              q_tile=tile)))
        return plan

    eng.scheduler.prepare_step = spy
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
        assert steps < 500
    return rids, steps, want_live


def test_every_step_is_a_span_cut_into_its_phases(tiny_model):
    """(a) N steps, N ``serve.step`` records, each with its five phases
    inside its interval, in order, summing to no more than it."""
    eng = _engine(tiny_model, max_num_seqs=4)
    mark = _mark()
    _, steps, _ = _run_counting(eng, _prompts(6))
    step_recs = _since(mark, "serve.step")
    assert len(step_recs) == steps
    recs = _since(mark)
    for s in step_recs:
        kids = sorted((r for r in recs if r.parent_id == s.id),
                      key=lambda r: r.t0_ns)
        assert [k.name for k in kids] == list(PHASES)
        assert s.t0_ns <= kids[0].t0_ns and kids[-1].t1_ns <= s.t1_ns
        for a, b in zip(kids, kids[1:]):
            assert a.t1_ns <= b.t0_ns
        assert sum(k.t1_ns - k.t0_ns for k in kids) <= s.t1_ns - s.t0_ns
        assert s.attrs["max_num_seqs"] == 4
        assert s.attrs["num_pages"] == eng.pool.capacity
        assert 0 <= s.attrs["used_pages"] <= s.attrs["num_pages"]
    # read at the step's end: the last step has freed every page
    assert step_recs[0].attrs["used_pages"] > 0 == \
        step_recs[-1].attrs["used_pages"]
    snap = eng.metrics_snapshot()["spans"]
    assert snap["serve.step"]["count"] >= steps
    assert set(PHASES) <= set(snap)


def test_counts_agree_with_the_programs_counters(tiny_model):
    """(b) the counts on the spans against the counters over the same
    steps, and ``live_kv_tokens`` against the scheduler's plan."""
    eng = _engine(tiny_model, max_num_seqs=3)
    before = eng.metrics_snapshot()
    mark = _mark()
    rids, steps, want_live = _run_counting(eng, _prompts(7, seed=1),
                                           max_new=5)
    after = eng.metrics_snapshot()
    step_recs = _since(mark, "serve.step")

    def delta(k):
        return after[k] - before[k]
    # a decode row emits a token, and so does a prompt's last chunk
    assert sum(s.attrs["decode_tokens"] for s in step_recs) + 7 \
        == delta("tokens_generated") == 7 * 5
    assert len(_since(mark, "serve.dispatch")) == delta("host_dispatches")
    assert sum(r.attrs["chunks"] for r in _since(mark, "serve.prefill")) \
        == delta("prefill_chunks")
    assert [(s.attrs["live_kv_tokens"],
             s.attrs["prefill_tokens"] + s.attrs["decode_tokens"],
             s.attrs["attn_kv_tokens_read"], s.attrs["attn_kv_walks"])
            for s in step_recs] == want_live
    # the 8-token chunks (q_block 8) are walked a block at a time: at
    # most a walk a query token, at least a walk a row
    assert all(s.attrs["rows"] <= s.attrs["attn_kv_walks"]
               <= s.attrs["prefill_tokens"] + s.attrs["decode_tokens"]
               for s in step_recs)
    # the walk covers every live token at least once
    assert all(s.attrs["attn_kv_tokens_read"] >= s.attrs["live_kv_tokens"]
               for s in step_recs)
    assert sum(s.attrs["prefill_tokens"] for s in step_recs) \
        == sum(len(eng._seqs[r].prompt_ids) for r in rids)
    # three row slots for seven requests: full at first, then draining
    assert step_recs[0].attrs["rows"] == 3 == step_recs[0].attrs["max_num_seqs"]
    assert all(s.attrs["rows"] <= 3 for s in step_recs)


@pytest.mark.parametrize("kw", [{}, {"burst_tokens": 4}],
                         ids=["ragged", "burst"])
def test_a_step_counts_the_rows_that_sample_and_mask(tiny_model, kw):
    """``sampled_rows`` / ``masked_rows`` on ``serve.step`` are the live
    rows with ``temperature`` > 0 and with a top-k or nucleus mask: a
    step with one sampling row counts 1 and is no short step to
    ``sampler_short_step_pct``; greedy steps are."""
    from benchmark import run as harness
    eng = _engine(tiny_model, max_num_seqs=4, **kw)
    mark = _mark()
    eng.add_request([5, 6, 7, 8], max_new_tokens=4, request_id="g")
    eng.add_request([9, 8, 7], max_new_tokens=9, temperature=0.8,
                    top_k=20, request_id="s")
    eng.add_request([1, 2, 3], max_new_tokens=4, temperature=0.0,
                    top_p=0.5, request_id="m")
    eng.run(max_steps=100)
    eng.add_request([4, 4, 4], max_new_tokens=3, request_id="g2")
    eng.run(max_steps=100)
    step_recs = [s for s in _since(mark, "serve.step") if "rows" in s.attrs]
    got = [(s.attrs["sampled_rows"], s.attrs["masked_rows"])
           for s in step_recs]
    assert got[0] == (1, 2)                 # all three aboard
    assert (1, 1) in got                    # "s" outlives the other two
    assert got[-1] == (0, 0)                # "g2" alone
    short = sum(n == 0 for n, _ in got)
    assert 0 < short < len(got)
    run = {"step_s": [0.0] * len(step_recs), "end_to_end": {},
           "trace": None, "peaks": None}
    assert harness.read_layer_metric("sampler_short_step_pct", run) \
        == pytest.approx(100.0 * short / len(got))


def test_the_q_per_walk_reader_on_a_canned_log(monkeypatch):
    """``ragged_attn_q_per_walk``: mean query tokens over mean
    ``attn_kv_walks`` over the window's steps, on records made by hand;
    a log from before the count reads nothing."""
    from benchmark import run as harness
    ms = 1_000_000

    def log(with_walks):
        recs = [(1, 0, "serve.step", 0, 10 * ms,
                 dict(prefill_tokens=0, decode_tokens=9, attn_kv_walks=9))]
        # in the window: 256 + 20 tokens in 4 + 20 walks, 64 + 30 in
        # 1 + 30, then a step that launched nothing
        for i, (p, d, w) in enumerate([(256, 20, 24), (64, 30, 31)]):
            attrs = dict(prefill_tokens=p, decode_tokens=d)
            if with_walks:
                attrs["attn_kv_walks"] = w
            recs.append((i + 2, 0, "serve.step", (i + 1) * 10 * ms,
                         (i + 2) * 10 * ms, attrs))
        recs.append((4, 0, "serve.step", 30 * ms, 40 * ms, None))
        return recs

    run = {"step_s": [0.01] * 3, "end_to_end": {}, "trace": None,
           "peaks": None}
    for with_walks, want in ((True, (276 + 94) / (24 + 31)), (False, None)):
        monkeypatch.setattr(spans, "records",
                            lambda name=None, r=log(with_walks): list(r))
        monkeypatch.setattr(spans, "open_spans", lambda name=None: [])
        got = harness.read_layer_metric("ragged_attn_q_per_walk", run)
        assert got == (want and pytest.approx(want))


def test_a_requests_life_is_queue_then_prefill(tiny_model):
    """(c) one ``serve.queue`` and one ``serve.prefill`` a finished
    request, meeting at admission, and together the engine's own time to
    the first token."""
    eng = _engine(tiny_model, max_num_seqs=2)
    mark = _mark()
    rids, _, _ = _run_counting(eng, _prompts(5, lo=10, hi=40, seed=2))
    queue = {r.attrs["request"]: r for r in _since(mark, "serve.queue")}
    prefill = {r.attrs["request"]: r for r in _since(mark, "serve.prefill")}
    assert len(_since(mark, "serve.queue")) == len(rids) == len(queue)
    assert len(_since(mark, "serve.prefill")) == len(rids) == len(prefill)
    for rid in rids:
        q, p, seq = queue[rid], prefill[rid], eng._seqs[rid]
        assert q.t1_ns == p.t0_ns
        ttft_s = seq.first_token_at - seq.arrival
        assert (p.t1_ns - q.t0_ns) / 1e9 == pytest.approx(ttft_s, abs=2e-3)
        # a chunk of 8 a step
        assert p.attrs["chunks"] == -(-len(seq.prompt_ids) // 8)
    # two row slots: the third request waited for a whole request
    assert queue[rids[2]].t1_ns - queue[rids[2]].t0_ns \
        > queue[rids[0]].t1_ns - queue[rids[0]].t0_ns
    assert not eng._life


def test_the_queue_span_is_the_wait_the_tracer_reports(tiny_model):
    """On one clock (``now_fn`` = ``perf_counter``) the ``serve.queue``
    span is as long as the ``queue_s`` of the tracer's ``admission``."""
    from paddle_tpu.serving import RequestTracer
    tracer = RequestTracer()
    eng = _engine(tiny_model, tracer=tracer, max_num_seqs=2)
    mark = _mark()
    rids, _, _ = _run_counting(eng, _prompts(4))
    queued = _since(mark, "serve.queue")
    assert len(queued) == len(rids)
    for r in queued:
        adm = [d for _, k, d in tracer.spans(r.attrs["request"])
               if k == "admission"]
        assert adm[0]["queue_s"] == pytest.approx(
            (r.t1_ns - r.t0_ns) / 1e9, abs=2e-3)


def test_a_request_that_leaves_early_closes_its_span(tiny_model):
    eng = _engine(tiny_model, max_num_seqs=1)
    mark = _mark()
    a = eng.add_request(list(range(20)), max_new_tokens=3)
    b = eng.add_request(list(range(9)), max_new_tokens=3)
    eng.step()
    waiting = [r for r in spans.open_spans("serve.queue")
               if r.attrs["request"] == b]
    assert len(waiting) == 1 and waiting[0].t1_ns is None
    eng.cancel(b)
    eng.cancel(a)
    last = {r.attrs["request"]: r.name for r in _since(mark)
            if r.attrs and "request" in r.attrs}
    assert last == {b: "serve.queue", a: "serve.prefill"}
    assert not eng._life and not [
        r for r in spans.open_spans() if r.attrs.get("request") in (a, b)]


def test_preemption_reopens_the_queue_span(tiny_model):
    """The storm of tests/test_tracing.py: a preempted row waits again,
    and no span of a resolved request stays open."""
    rng = np.random.default_rng(0)
    eng = _engine(tiny_model, max_len=32, num_pages=11, max_num_seqs=4,
                  high_watermark=0.85, low_watermark=0.4, chunk_size=None)
    mark = _mark()
    for w in range(6):
        for i in range(5):
            n = int(rng.integers(4, 11))
            eng.add_request(rng.integers(0, 128, (n,)).tolist(),
                            max_new_tokens=int(rng.integers(6, 11)),
                            request_id=f"storm-{w}-{i}")
        for _ in range(12):
            eng.step()
    eng.run(max_steps=5000)
    preempted = eng.metrics.preemptions.value
    assert preempted >= 5
    assert len(_since(mark, "serve.queue")) == 30 + preempted
    assert not eng._life


@pytest.mark.parametrize("kind,kw", [
    ("burst", {"burst_tokens": 4}),
    ("spec", {"max_num_seqs": 2, "spec_tokens": 3}),
])
def test_burst_and_spec_steps_are_cut_into_phases_too(tiny_model, kind, kw):
    if kind == "spec":
        kw = dict(kw, draft_model=tiny_model)
    eng = _engine(tiny_model, **kw)
    mark = _mark()
    eng.add_request([5, 6, 7, 5, 6, 7], max_new_tokens=8)
    eng.run(max_steps=100)
    step_recs = _since(mark, "serve.step")
    assert len(_since(mark, "serve.dispatch")) == \
        eng.metrics.host_dispatches.value
    assert sum(s.attrs["decode_tokens"] for s in step_recs) >= 7
    assert step_recs[0].attrs["prefill_tokens"] == 6
    recs = _since(mark)
    drafted = 0
    for s in step_recs:
        kids = [r.name for r in sorted(
            (r for r in recs if r.parent_id == s.id), key=lambda r: r.t0_ns)]
        want = list(PHASES)
        if "serve.draft" in kids:       # a speculative round
            want.insert(1, "serve.draft")
            drafted += 1
        assert kids == want
    if kind == "spec":
        assert drafted == eng.metrics.spec_rounds.value > 0
    else:
        assert drafted == 0 and eng.metrics.burst_launches.value > 0 \
            and len(step_recs) < 8
        # one row aboard: every step after the prompt's walks the row's
        # context once a token it yields, a burst as a ragged step
        assert all(s.attrs["attn_kv_walks"] == s.attrs["decode_tokens"]
                   for s in step_recs[1:])


def test_spans_share_the_profilers_clock(tiny_model, tmp_path):
    """(d) inside a profiler session every ``serve.step`` of the log is
    an event of the same length on ``/host:CPU``, the plane whose clock
    the device lines share."""
    import jax
    from jax.profiler import ProfileData
    eng = _engine(tiny_model)
    eng.add_request(list(range(12)), max_new_tokens=8)
    eng.step()                              # compile outside the trace
    mark = _mark()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            eng.step()
    finally:
        jax.profiler.stop_trace()
    logged = _since(mark, "serve.step")
    assert len(logged) == 3
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    events = sorted((e for line in host.lines for e in line.events
                     if e.name == "serve.step"), key=lambda e: e.start_ns)
    assert len(events) == 3
    for e, r in zip(events, logged):
        assert abs(e.duration_ns - (r.t1_ns - r.t0_ns)) < 0.5e6
    names = {e.name for line in host.lines for e in line.events}
    assert set(PHASES) <= names
    # the gaps between the steps agree too: one clock, up to an offset
    gaps_t = [b.start_ns - a.start_ns for a, b in zip(events, events[1:])]
    gaps_l = [b.t0_ns - a.t0_ns for a, b in zip(logged, logged[1:])]
    for a, b in zip(gaps_t, gaps_l):
        assert abs(a - b) < 0.5e6


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------

def test_train_step_spans_and_one_compile_a_specialization():
    """(f) three calls, three ``train.step`` and one ``train.compile``;
    another batch shape compiles again."""
    paddle.seed(3)
    m = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=128))
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, lambda x: m(x, labels=x)[1], opt)
    ids = paddle.to_tensor(np.arange(32).reshape(2, 16) % 128, dtype="int64")
    mark = _mark()
    for _ in range(3):
        step(ids)
    steps = _since(mark, "train.step")
    assert len(steps) == 3
    compiles = _since(mark, "train.compile")
    assert len(compiles) == 1 and compiles[0].parent_id == steps[0].id
    assert step.last_compile_ms == pytest.approx(
        (compiles[0].t1_ns - compiles[0].t0_ns) / 1e6)
    recs = _since(mark)
    for i, s in enumerate(steps):
        kids = [r.name for r in sorted(
            (r for r in recs if r.parent_id == s.id), key=lambda r: r.t0_ns)]
        # a specialization's first call also hands its compiled handle
        # to profiler/phases.py, once (PR 39)
        assert kids == ["train.gather"] + (
            ["train.dispatch"] if i else ["train.compile", "train.register"])
    wide = paddle.to_tensor(np.arange(48).reshape(2, 24) % 128,
                            dtype="int64")
    step(wide)
    assert len(_since(mark, "train.compile")) == 2
    assert len(_since(mark, "train.step")) == 4


# ---------------------------------------------------------------------------
# the span classes the package had
# ---------------------------------------------------------------------------

def test_record_event_and_compile_event_reach_the_native_recorder():
    """(g) under a ``Profiler`` both reach the native recorder as
    before; the program's ``compile_event`` is in the log either way, a
    user's ``RecordEvent`` never (the ring is the program's own)."""
    from paddle_tpu import profiler
    from paddle_tpu.core import native as nv
    nv.ensure_loaded()
    mark = _mark()
    with profiler.RecordEvent("t.user_off"):
        with profiler.compile_event("t.compile_off"):
            pass
    assert [r.name for r in _since(mark)] == ["t.compile_off"]
    if not nv.AVAILABLE:
        pytest.skip("native runtime not built")
    nv.prof_clear()
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as prof:
        with profiler.RecordEvent("t.user_span"):
            with profiler.compile_event("t.compile") as ev:
                pass
        ev2 = profiler.RecordEvent("t.begin_end")
        ev2.begin()
        ev2.end()
    native = [e[0] for e in prof.events()]
    assert {"t.user_span", "t.compile", "t.begin_end"} <= set(native)
    assert "t.user_off" not in native and "t.compile_off" not in native
    mine = {r.name: r for r in _since(mark)}
    assert set(mine) == {"t.compile_off", "t.compile"}
    assert ev.ms == pytest.approx(
        (mine["t.compile"].t1_ns - mine["t.compile"].t0_ns) / 1e6)


def test_a_record_event_lands_on_the_profilers_timeline(tmp_path):
    """A user's ``RecordEvent`` inside a ``jax.profiler`` session is an
    event on ``/host:CPU``, beside the program's own spans."""
    import jax
    from jax.profiler import ProfileData
    from paddle_tpu import profiler
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent("t.user_traced"):
            with span("t.program_traced"):
                pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    names = {e.name for line in host.lines for e in line.events}
    assert {"t.user_traced", "t.program_traced"} <= names


def test_one_span_primitive_in_the_program():
    """``serving/`` and ``jit/`` open spans through ``profiler.spans``;
    the profiler's and the native recorder's calls stay in their homes."""
    import re
    root = os.path.dirname(os.path.abspath(paddle.__file__))
    hits = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        rel = os.path.relpath(path, root)
        if rel.startswith(("profiler" + os.sep,
                           os.path.join("core", "native"))):
            continue
        with open(path) as f:
            if re.search(r"prof_begin|TraceAnnotation", f.read()):
                hits.append(rel)
    assert not hits
