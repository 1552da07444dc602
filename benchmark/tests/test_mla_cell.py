"""The cells ``dots-vlm1-inst.long-doc`` and
``smollm2-1.7b.pretrain-2k-dp2tp2``: their files against the catalog's
numbers and the program's config class, the counts the latent kernel's
roofline is taken against, the new readers on plain records and events,
and a rehearsal of ``long-doc`` at a small size through ``run.py``."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import build, mla_costs, moe_costs, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "dots-vlm1-inst.long-doc"
SHARDED = "smollm2-1.7b.pretrain-2k-dp2tp2"
NEW_METRICS = {"latent_attn_dev_pct", "latent_attn_roofline",
               "latent_kv_bytes_per_token"}
#: the language model's published settings (the catalog's row of
#: dots.vlm1.inst, read from its config.json)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "dots_vlm", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280}


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_the_file_keeps_every_published_key_it_does_not_declare_reduced():
    cfg = _json("benchmark", "configs", "dots-vlm1-inst.json")
    cls = build.load_attr(cfg["model"]["config_class"])
    build.published_check(cls)(cfg)                  # raises on a drop
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert changed | {"vision_tower"} == set(cfg["reduced"])
    for key, cut in cfg["reduced"].items():
        if key in PUBLISHED:
            assert (cut["published"], cut["run"]) == (PUBLISHED[key],
                                                      cfg[key])
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "dots-vlm1-inst")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    # the floors: a period + four sparse layers, 8 experts, 1/8 vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"]


def test_the_cells_entries():
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1 and cells[SHARDED]["chips"] == 4
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == [SHARDED]
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert NEW_METRICS | {"moe_expert_roofline", "moe_expert_dev_pct",
                          "kv_pool_used_pct"} <= mine
    # another kernel, another pool: not this cell's
    assert not mine & {"ragged_attn_dev_pct", "ragged_attn_roofline",
                       "ragged_attn_roofline.windowed",
                       "ragged_attn_kv_reread_x", "kv_held_bytes_per_token"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert e2e == {"ttft_p95_ms", "itl_p95_ms", "serve_tok_s", "setup_s"}
    sharded = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
               if SHARDED in m.get("workloads", [SHARDED])}
    assert sharded == {"train_tok_s", "setup_s", "train_step_p50_ms",
                       "train_mfu_pct", "fused_adamw_roofline",
                       "device_idle_pct.train", "train_host_ms_p50",
                       "collective_exposed_pct"}
    mix = _json("benchmark", "traffic", "pretrain-2k-dp2tp2.json")
    base = _json("benchmark", "traffic", "pretrain-2k.json")
    assert (mix["sharding"], mix["batch"], mix["seq"]) \
        == ("dp=2,tp=2", 2 * base["batch"], base["seq"])
    assert {k: mix[k] for k in ("kind", "trace_after_s", "trace_s")} \
        == {k: base[k] for k in ("kind", "trace_after_s", "trace_s")}
    doc = _json("benchmark", "traffic", "long-doc.json")
    engine = _json("benchmark", "configs", "dots-vlm1-inst.json")["engine"]
    assert engine["max_len"] == doc["prompt_len"]["max"] \
        + doc["answer_len"]["max"]


def test_counts_of_the_published_widths():
    cfg = _json("benchmark", "configs", "dots-vlm1-inst.json")
    assert mla_costs.latent_width(cfg) == 576
    assert mla_costs.row_bytes(cfg) == 1152              # 576 x 2 B
    assert mla_costs.flops_per_pair(cfg) == 2 * 128 * 1088 == 278_528
    # at the ridge: 278,528 FLOP over 1,152 B a key, against 197e12 / 819e9
    assert 241 < mla_costs.flops_per_pair(cfg) / mla_costs.row_bytes(cfg) \
        < 242
    chip = peaks.PEAKS["TPU v5 lite"]
    # a decode step of 32 rows at 5,000 tokens: each token's row is read
    # once a layer and seen by one query, so the bytes bound it
    live = 32 * 5000
    got = mla_costs.attention_floor_s(cfg, chip, 6 * live, live)
    assert got == pytest.approx(6 * live * 278_528 / 197e12)
    assert got == pytest.approx(6 * live * 1152 / 819e9, rel=0.01)
    # a 256-token chunk behind 8,000 tokens: the FLOPs bound it
    pairs = 6 * (256 * 8000 + 256 * 257 // 2)
    assert mla_costs.attention_floor_s(cfg, chip, pairs, 8256) \
        == pytest.approx(pairs * 278_528 / 197e12)
    # the routed product at hidden 7168 (moe_costs reads the same file)
    assert moe_costs.sparse_layers(cfg) == 5
    assert moe_costs.expert_bytes(cfg, 1, 0) == 3 * 7168 * 2048 * 2
    assert moe_costs.expert_flops(cfg, 2) == 2 * 6 * 7168 * 2048


def _step(i, t0_ms, **attrs):
    return (i, None, "serve.step", int(t0_ms * 1e6), int((t0_ms + 50) * 1e6),
            attrs)


def test_latent_kv_bytes_per_token_reads_the_steps_own_counts(monkeypatch):
    from benchmark import program_log
    reader = _reader("latent_kv_bytes_per_token")
    page = 6 * 16 * 1280
    records = [
        _step(1, 0, used_pages=9, latent_bytes_held=10 * page,
              live_kv_tokens=150),
        _step(2, 60, used_pages=20, latent_bytes_held=20 * page,
              live_kv_tokens=320),
        _step(3, 120),                               # launched nothing
    ]
    w = program_log.cut(records, [], 3, "serve.step")
    monkeypatch.setattr(program_log, "window", lambda run, name: w)
    got = reader.read({"step_s": [0.05] * 3})
    assert got == pytest.approx((10 * page / 150 + 20 * page / 320) / 2)
    assert got == pytest.approx(7936)          # 7,680 + part-filled pages
    # a pool of (K, V) pages counts no latent_bytes_held: nothing to read
    plain = program_log.cut([_step(1, 0, used_pages=10, live_kv_tokens=99)],
                            [], 1, "serve.step")
    monkeypatch.setattr(program_log, "window", lambda run, name: plain)
    assert reader.read({"step_s": [0.05]}) is None


def test_latent_readers_find_nothing_on_another_configuration():
    for name in NEW_METRICS:
        assert _reader(name).read(
            {"config": {"hidden_size": 64}, "trace": None, "peaks": None,
             "step_s": []}) is None


def test_latent_attn_roofline_over_the_traced_steps(monkeypatch):
    """Two traced steps of six kernel events each: the floor is the mean
    step's pairs over the peak, the time the kernel's seconds a step."""
    from benchmark import program_log
    reader = _reader("latent_attn_roofline")
    cfg = _json("benchmark", "configs", "dots-vlm1-inst.json")
    pairs = [6 * 1_000_000, 6 * 1_400_000]
    records = [_step(i + 1, 3000 + 60 * i, attn_qk_pairs=p,
                     live_kv_tokens=160_000)
               for i, p in enumerate(pairs)]
    w = program_log.cut(records, [], 2, "serve.step")
    w.lo_ns = 0                      # the window opened 3 s before them
    monkeypatch.setattr(program_log, "window", lambda run, name: w)
    trace = {"op_seconds": {f"ragged_latent_attention.{i}": 0.004
                            for i in range(12)},
             "op_counts": {f"ragged_latent_attention.{i}": 1
                           for i in range(12)}}
    got = reader.read({"config": cfg, "trace": trace,
                       "peaks": peaks.PEAKS["TPU v5 lite"],
                       "traffic": {"trace_after_s": 3},
                       "step_s": [0.05] * 2})
    floor = 6 * 1_200_000 * 278_528 / 197e12         # 10.2 ms: FLOP-bound
    assert floor > 160_000 * 6 * 1152 / 819e9
    assert got == pytest.approx(100 * floor / 0.024)


def test_collective_exposed_pct_counts_what_nothing_else_covers():
    reader = _reader("collective_exposed_pct")
    ms = 1e6
    line = [["fusion.1", 0 * ms, 10 * ms],
            ["all-reduce.3", 10 * ms, 4 * ms],        # alone: 4 exposed
            ["all-gather.7", 20 * ms, 6 * ms],        # fusion.2 covers 2
            ["fusion.2", 24 * ms, 8 * ms],
            ["while.1", 30 * ms, 30 * ms],            # a container: covers
            ["reduce-scatter.2", 40 * ms, 5 * ms],    # nothing; 5 exposed
            ["all-reduce-start.1", 50 * ms, 1 * ms],  # 1 exposed
            ["convolution.4", 52 * ms, 6 * ms],       # under the transfer
            ["all-reduce-done.1", 58 * ms, 2 * ms],   # 2 exposed
            ["all-reduce.9", 95 * ms, 20 * ms]]       # 5 inside the window
    events = {"device": {"/device:TPU:1": [["all-reduce.1", 0, 100 * ms]],
                         "/device:TPU:0": line},
              "spans": [["bench.trace_window", 0.0, 100 * ms]]}
    exposed, total = reader.exposed_ns(line, 0, 100 * ms)
    assert total == (4 + 6 + 5 + 1 + 2 + 5) * ms
    assert exposed == (4 + 4 + 5 + 1 + 2 + 5) * ms
    assert reader.read({"events": events}) == pytest.approx(21.0)
    # one chip: no collective in the trace, nothing to report
    alone = {"device": {"/device:TPU:0": [["fusion.1", 0, 10 * ms]]},
             "spans": events["spans"]}
    assert reader.read({"events": alone}) is None
    assert reader.read({"events": None}) is None


def test_a_rehearsal_of_long_doc_at_a_small_size(tmp_path):
    """The real configuration's keys at small widths, the real traffic
    file's shape at small lengths, the real metric entries: the path the
    chip run takes, through ``run.py``, with ``correct`` decided by the
    reference. A rehearsal names what it would report and gives no
    value; the trace's device metrics have nothing to read on a CPU."""
    cfg = _json("benchmark", "configs", "dots-vlm1-inst.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
               v_head_dim=16, num_hidden_layers=4,
               mlp_layer_types=["dense"] + ["sparse"] * 3,
               n_routed_experts=4, router_width=16, expert_offset=4,
               n_group=4, topk_group=2, num_experts_per_tok=4, vocab_size=64,
               dtype="float32",
               rope_scaling=dict(cfg["rope_scaling"], factor=4,
                                 original_max_position_embeddings=32),
               logit_tol={"mean": 1e-4, "max": 1e-3},
               engine={"max_len": 128, "max_num_seqs": 4, "page_size": 4,
                       "chunk_size": 16, "q_block": 4,
                       "prefix_caching": False, "num_pages": 128})
    mix = _json("benchmark", "traffic", "long-doc.json")
    mix.update(rate_rps=2.0, warm_s=1, trace_after_s=0.5, trace_s=1,
               prompt_len=dict(mix["prompt_len"], median=40, min=12, max=100),
               answer_len=dict(mix["answer_len"], median=6, min=3, max=10))
    real = _json("BENCHMARK.json")
    bench = dict(real, paths=["data"], configs=[{
        "name": "small", "source": cfg["source"],
        "file": "data/configs/small.json", "reduced": [], "why": "a test"}],
        workloads=[{"name": "small.long-doc", "config": "small",
                    "traffic": "long-doc", "chips": 1, "why": "a test"}])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=["small.long-doc"])
                        for m in real[group]
                        if CELL in m.get("workloads", [CELL])]
    os.makedirs(tmp_path / "data" / "configs")
    os.makedirs(tmp_path / "data" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "data" / "configs" / "small.json").write_text(
        json.dumps(cfg))
    (tmp_path / "data" / "traffic" / "long-doc.json").write_text(
        json.dumps(mix))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--benchmark-json", str(tmp_path / "BENCHMARK.json"),
         "--workload", "small.long-doc", "--seed", "3000000029",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "small.long-doc"
    assert last["correct"] is True, p.stderr[-1500:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"latent_kv_bytes_per_token", "kv_pool_used_pct",
            "batch_rows_pct", "tokens_per_dispatch",
            "serve_wait_ms_p50"} <= set(last["would_report"])
