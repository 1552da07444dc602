"""The yardstick's arithmetic on inputs small enough to check by hand."""
import json
import os

import pytest

from benchmark import latency, peaks, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


# --- stamps -> serving metrics ---------------------------------------------

def test_ttft_counts_from_due_time_and_censors_at_close():
    due = {"a": 10.0, "b": 12.0, "c": 19.0, "early": 5.0, "late": 20.0}
    stamps = {"a": [11.0, 11.5], "b": [], "c": [21.0], "early": [6.0],
              "late": [20.5]}
    got, censored = latency.ttft_samples(due, stamps, 10.0, 20.0)
    # a: 1.0; b never answered: age 8.0; c answered after the close: age
    # 1.0; "early" and "late" are not due inside [10, 20)
    assert sorted(got) == [1.0, 1.0, 8.0]
    assert censored == 2


def test_gaps_pool_tokens_emitted_inside_the_window():
    stamps = {"a": [9.0, 10.5, 11.0, 20.5], "b": [12.0], "c": [13.0, 13.25]}
    # a: 10.5-9.0 (predecessor before the window counts), 11.0-10.5; its
    # 20.5 token lies outside. b has no gap. c: 0.25
    assert sorted(latency.gap_samples(stamps, 10.0, 20.0)) == [0.25, 0.5, 1.5]
    assert latency.tokens_in(stamps, 10.0, 20.0) == 5


def test_percentile_is_nearest_rank():
    xs = list(range(1, 21))
    assert latency.percentile(xs, 95) == 19
    assert latency.percentile(xs, 50) == 10
    assert latency.percentile([7.0], 95) == 7.0


# --- traffic -----------------------------------------------------------------

MIX = {"kind": "serve_open_loop", "arrivals": {"process": "poisson"},
       "rate_rps": 2.0, "warm_s": 5, "set_seed": 0,
       "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.8,
                      "min": 8, "max": 100},
       "answer_len": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                      "min": 2, "max": 16}}


def _plain(reqs):
    return [(r.due, tuple(r.prompt), r.max_new_tokens, r.in_window)
            for r in reqs]


def test_same_seed_same_requests_other_seed_other_requests():
    a = traffic.schedule(MIX, 512, 3000000001, 10)
    b = traffic.schedule(MIX, 512, 3000000001, 10)
    c = traffic.schedule(MIX, 512, 3000000002, 10)
    assert _plain(a) == _plain(b)
    assert _plain(a) != _plain(c)


def test_every_seed_offers_the_same_schedule_with_its_own_tokens():
    a = traffic.schedule(MIX, 512, 1, 10)
    c = traffic.schedule(MIX, 512, 2, 10)
    assert len(a) == len(c) == 2 * 5 + 2 * 10
    assert [(r.due, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due, len(r.prompt), r.max_new_tokens) for r in c]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    win = [r for r in a if r.in_window]
    assert len(win) == 20 and all(5 <= r.due < 15 for r in win)
    assert all(8 <= len(r.prompt) <= 100 and 2 <= r.max_new_tokens <= 16
               for r in a)
    assert len({len(r.prompt) for r in a}) > 10       # lengths do vary


def test_rate_override_and_bursty_arrivals_and_shared_prefix():
    mix = dict(MIX, arrivals={"process": "gamma", "cv": 3.0},
               shared_prefix={"documents": 2, "len": 8})
    reqs = traffic.schedule(mix, 512, 1, 10, rate=4.0)
    assert len(reqs) == 4 * 5 + 4 * 10
    heads = {tuple(r.prompt[:8]) for r in reqs}
    assert len(heads) == 2


def test_train_batches_follow_seed_and_step():
    a = traffic.train_batch(512, 5, 0, 2, 16)
    assert a.shape == (2, 16) and (a == traffic.train_batch(512, 5, 0, 2, 16)).all()
    assert (a != traffic.train_batch(512, 5, 1, 2, 16)).any()
    assert (a != traffic.train_batch(512, 6, 0, 2, 16)).any()


# --- peaks, operation and byte counts --------------------------------------

def _cfg(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_parameter_and_flop_counts_at_the_published_widths():
    smol, mistral = _cfg("smollm2-1.7b"), _cfg("mistral-7b")
    assert peaks.dense_decoder_params(smol) == 503_343_104
    assert peaks.dense_decoder_params(mistral) == 2_885_783_552
    # 6 x parameters + 12 L h s
    assert peaks.train_useful_flops_per_token(smol, 2048) == \
        6 * 503_343_104 + 12 * 6 * 2048 * 2048
    assert peaks.adamw_bytes(1000) == 28_000


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


# --- trace reduction ---------------------------------------------------------

def test_reduction_on_hand_made_events():
    ev = {"device": {"/device:TPU:0": [
        ["fusion.1", 90.0, 20.0],          # clipped to [100, 110)
        ["fusion.1", 120.0, 30.0],
        ["fused_adamw", 140.0, 20.0],      # overlaps fusion.1 by 10
        ["copy.2", 190.0, 40.0]]},         # clipped to [190, 200)
        "spans": [["bench.trace_window", 100.0, 100.0],
                  ["bench.step", 100.0, 15.0],
                  ["bench.read_loss", 160.0, 30.0]]}
    r = xplane.reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    # union: [100,110) + [120,160) + [190,200) = 60
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["idle_pct"] == pytest.approx(40.0)
    assert r["op_seconds"]["fusion.1"] == pytest.approx(40e-9)
    assert xplane.op_seconds(r, "fused_adamw") == pytest.approx(20e-9)
    assert xplane.op_count(r, "fused_adamw") == 1
    # gaps [110,120) and [160,190): bench.step covers [110,115),
    # bench.read_loss covers [160,190)
    gaps = r["idle_seconds_by_span"]
    assert gaps["bench.step"] == pytest.approx(5e-9)
    assert gaps["bench.read_loss"] == pytest.approx(30e-9)
    assert gaps["(no span)"] == pytest.approx(5e-9)
    assert xplane.top(gaps, 1) == [["bench.read_loss", pytest.approx(30e-9)]]


def test_reduction_without_a_device_plane_returns_nothing():
    assert xplane.reduce({"device": {}, "spans": [
        ["bench.trace_window", 0.0, 10.0]]}) is None
    assert xplane.reduce({"device": {"/device:TPU:0": []}, "spans": []}) \
        is None


def _fixture():
    import gzip
    with gzip.open(os.path.join(HERE, "fixture_train_step.json.gz")) as f:
        return json.load(f)


def test_reduction_on_a_recorded_train_step():
    """One step cut from a v5e trace (see the fixture's ``about``). The
    busy union is checked against a brute-force timeline of 100 ns
    ticks, an independent way to the same number."""
    import numpy as np
    ev = _fixture()
    r = xplane.reduce(ev)
    (events,) = ev["device"].values()
    window = ev["spans"][0][2]
    ticks = np.zeros(int(window // 100) + 1, bool)
    for _, s, d in events:
        ticks[max(0, int(s // 100)):max(0, int((s + d) // 100))] = True
    brute_busy_s = ticks.sum() * 100e-9
    assert r["window_s"] == pytest.approx(window / 1e9)
    assert r["busy_s"] == pytest.approx(brute_busy_s, rel=2e-3)
    assert r["idle_pct"] == pytest.approx(1.63, abs=0.05)
    # per-name kernel time: one fused_adamw call a step, 21.26 ms
    assert xplane.op_count(r, "fused_adamw") == 1
    assert xplane.op_seconds(r, "fused_adamw") == pytest.approx(21.258e-3,
                                                                rel=1e-3)
    # its roofline share through the metric's own reader: 28 B x 503.3 M
    # parameters over 819 GB/s is 17.2 ms
    from benchmark import run as harness
    share = harness.read_layer_metric("fused_adamw_roofline", {
        "trace": r, "n_params": 503_343_104,
        "peaks": peaks.peaks_for("TPU v5 lite")})
    assert share == pytest.approx(80.95, abs=0.1)
    # idle gaps are charged to the host span that covers them
    gaps = r["idle_seconds_by_span"]
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert set(gaps) <= {"bench.step", "bench.feed_batch", "bench.read_loss",
                         "(no span)"}
    fams = dict(xplane.top_families(r["op_seconds"]))
    assert "fused_adamw" in fams and "fused_adamw.1" not in fams


def test_short_names_and_families():
    text = ("%ragged_paged_attention.12 = bf16[320,8,4,128]{3,2,1,0} "
            "custom-call(s32[40]{0} %x), custom_call_target=\"tpu_custom_call\"")
    assert xplane.short_name(text) == "ragged_paged_attention.12"
    assert xplane.family("ragged_paged_attention.12") == \
        "ragged_paged_attention"
    assert xplane.family("while") == "while"
    assert xplane.short_name("bench.step") == "bench.step"
