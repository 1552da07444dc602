"""The window cut out of the program's span log, each reader of it, and
the hand tool's innermost-span attribution, on records small enough to
check by hand."""
import pytest

from benchmark import peaks, program_log, xplane
from benchmark import run as harness

MS = 1_000_000


def rec(id, parent, name, t0_ms, t1_ms, **attrs):
    return (id, parent, name, int(t0_ms * MS),
            None if t1_ms is None else int(t1_ms * MS), attrs or None)


def step(id, t0, phases, self_ms, **attrs):
    """A ``serve.step`` at ``t0`` ms whose phases follow one another,
    then ``self_ms`` of its own; returns (records, end)."""
    out, t = [], t0
    for i, (name, ms) in enumerate(phases):
        out.append(rec(id + 1 + i, id, name, t, t + ms))
        t += ms
    out.append(rec(id, 0, "serve.step", t0, t + self_ms, **attrs))
    return out, t + self_ms


def serving_log():
    """One step before the window and three inside it: 170, 170 and 160
    ms; requests before, inside, still prefilling, still waiting, and
    one preempted mid-prefill."""
    log, t = step(10, 0, [("serve.plan", 9), ("serve.wait", 90)], 1,
                  rows=1, max_num_seqs=32)
    s1, t = step(20, t, [("serve.plan", 1), ("serve.assemble", 0.5),
                         ("serve.dispatch", 2.5), ("serve.wait", 164),
                         ("serve.commit", 1)], 1,
                 rows=16, max_num_seqs=32, prefill_tokens=90,
                 decode_tokens=10, live_kv_tokens=8000, used_pages=600,
                 num_pages=6000)
    s2, t = step(30, t, [("serve.plan", 2), ("serve.assemble", 0.5),
                         ("serve.dispatch", 3.5), ("serve.wait", 160),
                         ("serve.commit", 2), ("serve.commit", 1)], 1,
                 rows=24, max_num_seqs=32, prefill_tokens=100,
                 decode_tokens=20, live_kv_tokens=10000, used_pages=900,
                 num_pages=6000)
    s3, t = step(40, t, [("serve.plan", 3), ("serve.assemble", 1.5),
                         ("serve.dispatch", 2.5), ("serve.wait", 150),
                         ("serve.commit", 2)], 1,
                 rows=8, max_num_seqs=32, prefill_tokens=50,
                 decode_tokens=30, live_kv_tokens=12000, used_pages=300,
                 num_pages=6000)
    assert t == 600
    life = [
        rec(50, 0, "serve.queue", 50, 60, request="before"),
        rec(51, 0, "serve.prefill", 60, 300, request="before", chunks=4),
        rec(52, 0, "serve.queue", 110, 120, request="r1"),
        rec(53, 0, "serve.prefill", 120, 450, request="r1", chunks=3),
        rec(54, 0, "serve.queue", 200, 280, request="r2"),
        rec(56, 0, "serve.queue", 105, 106, request="r4"),
        rec(57, 0, "serve.prefill", 106, 300, request="r4", chunks=2),
        rec(58, 0, "serve.queue", 130, 140, request="r5"),
        rec(59, 0, "serve.prefill", 140, 200, request="r5", chunks=1),
        rec(60, 0, "serve.queue", 200, 230, request="r5"),
        rec(61, 0, "serve.prefill", 230, 400, request="r5", chunks=2),
        rec(70, 0, "train.step", 601, 603),
    ]
    still_open = [
        rec(55, 0, "serve.prefill", 280, None, request="r2", chunks=2),
        rec(62, 0, "serve.queue", 500, None, request="r3"),
    ]
    # the log's order is the order of the spans' ends
    done = sorted(log + s1 + s2 + s3 + life, key=lambda r: r[4])
    return done, still_open


@pytest.fixture
def serving(monkeypatch):
    from paddle_tpu.profiler import spans
    done, still_open = serving_log()
    monkeypatch.setattr(spans, "records", lambda name=None: list(done))
    monkeypatch.setattr(spans, "open_spans",
                        lambda name=None: list(still_open))
    return {"step_s": [0.17, 0.17, 0.16], "end_to_end": {}, "trace": None,
            "peaks": None}


def test_the_window_is_the_last_steps_with_their_children():
    done, still_open = serving_log()
    w = program_log.cut(done, still_open, 3, "serve.step")
    assert [s[0] for s in w.steps] == [20, 30, 40]
    assert (w.lo_ns, w.hi_ns) == (100 * MS, 600 * MS)
    assert w.step_ms() == [170, 170, 160]
    assert w.phase_ms("serve.commit") == [1, 3, 2]      # s2 has two
    assert w.self_ms() == [1, 1, 1]
    assert w.phase_ms("serve.draft") is None
    assert w.counts("rows") == [16, 24, 8]
    # all four steps: the window then starts at the first one's start
    w4 = program_log.cut(done, still_open, 4, "serve.step")
    assert w4.lo_ns == 0 and len(w4.steps) == 4
    # fewer steps in the log than the harness counted: nothing to read
    assert program_log.cut(done, still_open, 5, "serve.step") is None
    assert program_log.cut(done, still_open, 0, "serve.step") is None
    assert program_log.cut([], [], 3, "serve.step") is None


def test_the_windows_requests_and_the_unfinished_ones():
    done, still_open = serving_log()
    got = program_log.cut(done, still_open, 3, "serve.step").requests()
    assert set(got) == {"r1", "r2", "r3", "r4", "r5"}   # not "before"
    assert got["r1"] == {"queue_ms": 10, "prefill_ms": 330, "chunks": 3}
    # still prefilling at the window's end (600): its age, 320
    assert got["r2"] == {"queue_ms": 80, "prefill_ms": 320, "chunks": 2}
    # still waiting: its age, and no prefill yet
    assert got["r3"] == {"queue_ms": 100, "prefill_ms": 0, "chunks": 0}
    # preempted mid-prefill: both waits, both prefills
    assert got["r5"] == {"queue_ms": 40, "prefill_ms": 230, "chunks": 3}


@pytest.mark.parametrize("metric,want", [
    ("serve_plan_ms_p50", 2.0),
    ("serve_assemble_ms_p50", 0.5),
    ("serve_dispatch_ms_p50", 2.5),
    ("serve_commit_ms_p50", 3.0),         # commit 1, 3, 2 + 1 of self time
    ("serve_wait_ms_p50", 160.0),
    ("ttft_queue_p95_ms", 100.0),         # nearest rank of 5: the largest
    ("ttft_prefill_p95_ms", 330.0),
    ("step_prefill_token_pct", 80.0),     # 240 of 300 tokens
    ("batch_rows_pct", 50.0),             # mean of 50, 75, 25
    ("kv_pool_used_pct", 10.0),           # mean of 10, 15, 5
])
def test_each_reader_on_the_synthetic_window(serving, metric, want):
    assert harness.read_layer_metric(metric, serving) == pytest.approx(want)
    # a run without steps in its window reads nothing
    assert harness.read_layer_metric(metric, dict(serving, step_s=[])) is None


def test_train_host_time_reads_the_train_steps(monkeypatch):
    from paddle_tpu.profiler import spans
    log = [rec(1, 0, "train.step", 0, 50),
           rec(3, 2, "train.gather", 60, 61), rec(2, 0, "train.step", 60, 62),
           rec(4, 0, "train.step", 300, 303),
           rec(5, 0, "train.step", 550, 560)]
    monkeypatch.setattr(spans, "records", lambda name=None: list(log))
    monkeypatch.setattr(spans, "open_spans", lambda name=None: [])
    run = {"step_s": [0.25, 0.25, 0.25]}
    assert harness.read_layer_metric("train_host_ms_p50", run) == 3.0
    # a serving reader finds no step of its kind in a training run
    assert harness.read_layer_metric("serve_plan_ms_p50", run) is None


def test_the_traced_steps_are_cut_by_the_clock_and_the_traces_count():
    """The window's steps start at 100, 270 and 440 ms after a window
    start of 100 ms: a profiler started 0.15 s into the window saw the
    steps from the second on, as many as the trace counts."""
    done, still_open = serving_log()
    w = program_log.cut(done, still_open, 3, "serve.step")
    assert [s[0] for s in w.traced_steps(0.15, 2)] == [30, 40]
    assert [s[0] for s in w.traced_steps(0.15, 1)] == [30]
    assert [s[0] for s in w.traced_steps(0.0, 3)] == [20, 30, 40]
    assert w.counts("live_kv_tokens", w.traced_steps(0.15, 2)) == \
        [10000, 12000]
    # more steps in the trace than the window holds from there on, a
    # start after its last step, an empty trace: nothing to read
    assert w.traced_steps(0.15, 3) is None
    assert w.traced_steps(0.5, 1) is None
    assert w.traced_steps(0.0, 0) is None


def test_ragged_attn_roofline_on_fixture_style_numbers(serving):
    """Two traced steps of 12 layers at 117.5 ms of kernel time a step,
    the profiler started 0.15 s into the window: the second and third
    step, which read 10 and 12 thousand live tokens (the first read 8).
    11,000 x 49,152 B over 819 GB/s is 0.6602 ms, 0.5618 % of 117.5."""
    cfg = {"num_hidden_layers": 12, "num_key_value_heads": 8,
           "head_dim": 128, "hidden_size": 4096, "num_attention_heads": 32,
           "dtype": "bfloat16"}
    trace = {"op_seconds": {"ragged_paged_attention.12": 2 * 0.1175,
                            "copy.3": 1.0},
             "op_counts": {"ragged_paged_attention.12": 2 * 12,
                           "copy.3": 2}}
    run = dict(serving, config=cfg, trace=trace,
               traffic={"trace_after_s": 0.15, "trace_s": 0.34},
               peaks=peaks.peaks_for("TPU v5 lite"))
    got = harness.read_layer_metric("ragged_attn_roofline", run)
    assert got == pytest.approx(100 * (11000 * 49152 / 819e9) / 0.1175)
    assert got == pytest.approx(0.5618, abs=1e-4)
    for lacking in (dict(run, trace=None), dict(run, step_s=[]),
                    dict(run, traffic={"trace_after_s": 0.5})):
        assert harness.read_layer_metric("ragged_attn_roofline",
                                         lacking) is None


def test_a_program_without_the_log_reads_nothing(monkeypatch):
    """The parent of the PR that added the log: the import fails, the
    readers return None and raise nothing."""
    import sys
    monkeypatch.setitem(sys.modules, "paddle_tpu.profiler.spans", None)
    import paddle_tpu.profiler as profiler
    monkeypatch.delattr(profiler, "spans")
    run = {"step_s": [0.1], "end_to_end": {}, "trace": None, "peaks": None}
    assert program_log.window(run, "serve.step") is None
    assert harness.read_layer_metric("serve_wait_ms_p50", run) is None


# --- idle gaps by the program's spans ----------------------------------------

NESTED = {"device": {"/device:TPU:0": [["fusion.1", 100.0, 20.0],
                                       ["fusion.2", 150.0, 30.0]]},
          "spans": [["bench.trace_window", 100.0, 100.0],
                    ["bench.step", 110.0, 50.0],
                    ["serve.step", 112.0, 46.0],
                    ["serve.wait", 112.0, 13.0],
                    ["serve.commit", 125.0, 15.0],
                    ["bench.admit", 185.0, 5.0]]}


def test_idle_goes_to_the_innermost_covering_span():
    r = xplane.reduce(NESTED)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)
    # gaps [120, 150) and [180, 200). In the first: wait to 125, commit
    # to 140, then serve.step's own; bench.step covers all of it and is
    # charged none of it
    by = r["idle_seconds_by_span"]
    assert by == {"serve.wait": pytest.approx(5e-9),
                  "serve.commit": pytest.approx(15e-9),
                  "serve.step": pytest.approx(10e-9),
                  "bench.admit": pytest.approx(5e-9),
                  "(no span)": pytest.approx(15e-9)}
    # so the charges add up to the idle time, nested spans or not
    assert sum(by.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_innermost_pieces_are_disjoint_and_take_the_latest_start():
    pieces = xplane.innermost(NESTED["spans"][1:])
    assert pieces == [["bench.step", 110.0, 2.0],
                      ["serve.wait", 112.0, 13.0],
                      ["serve.commit", 125.0, 15.0],
                      ["serve.step", 140.0, 18.0],
                      ["bench.step", 158.0, 2.0],
                      ["bench.admit", 185.0, 5.0]]
    # two that start together: the one that ends first is the inner one
    assert xplane.innermost([["train.step", 0.0, 10.0],
                             ["train.gather", 0.0, 4.0]]) == [
        ["train.gather", 0.0, 4.0], ["train.step", 4.0, 6.0]]
    assert xplane.innermost([]) == []


def test_the_hand_tool_prints_the_same_attribution(monkeypatch, capsys):
    from benchmark import idle_by_program_span as tool
    monkeypatch.setattr(xplane, "load", lambda path: NESTED)
    assert tool.main(["tool", "some.xplane.pb"]) == 0
    out = capsys.readouterr().out
    assert "device idle 0.0000 s (50.00 %), 1 program steps" in out
    assert "serve.commit" in out and "30.0" in out     # 15 of 50 ns
