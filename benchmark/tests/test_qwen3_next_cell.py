"""The cell ``qwen3-next-80b-a3b.long-chat``: its files against the
catalog's numbers and the program's config class, the cut against the
floors of a configuration's size, the counts the gated-delta kernel's
roofline is taken against, the new readers on plain records and events,
and a rehearsal of the cell at a small size through ``run.py``."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

from benchmark import build, linear_costs, moe_costs, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "qwen3-next-80b-a3b.long-chat"
NEW_METRICS = {"linear_attn_dev_pct", "linear_attn_roofline",
               "serve_linear_dev_pct"}
#: the published settings (the catalog's row of Qwen3-Next-80B-A3B-Instruct,
#: read from its config.json)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


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
    cfg = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    cls = build.load_attr(cfg["model"]["config_class"])
    build.published_check(cls)(cfg)                  # raises on a drop
    changed = {k for k, v in PUBLISHED.items() if cfg[k] != v}
    assert changed == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "num_experts", "vocab_size"}
    for key, cut in cfg["reduced"].items():
        assert (cut["published"], cut["run"]) == (PUBLISHED[key], cfg[key])
    entry = next(c for c in _json("BENCHMARK.json")["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert entry["source"] == cfg["source"]


def test_the_cut_keeps_to_the_floors():
    """A whole period (and at least four layers), at least 8 experts, at
    least an eighth of the vocabulary; the router keeps its published
    width and picks."""
    cfg = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    assert n >= 4 and n % every == 0
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert (cfg["router_width"], cfg["num_experts_per_tok"]) == (512, 10)
    assert cfg["dtype"] == "bfloat16"


def test_mlp_layer_types_derive_from_the_published_keys():
    cfg = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    derived = ["dense" if i in cfg["mlp_only_layers"]
               or (i + 1) % cfg["decoder_sparse_step"] else "sparse"
               for i in range(cfg["num_hidden_layers"])]
    assert cfg["mlp_layer_types"] == derived == ["sparse"] * 12
    assert moe_costs.sparse_layers(cfg) == 12


def test_the_cells_entries():
    bench = _json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["chips"] == 1
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert NEW_METRICS | {
        "tokens_per_dispatch", "engine_step_p50_ms", "ragged_attn_dev_pct",
        "device_idle_pct.serve", "serve_plan_ms_p50", "serve_assemble_ms_p50",
        "serve_dispatch_ms_p50", "serve_commit_ms_p50", "serve_wait_ms_p50",
        "ttft_queue_p95_ms", "ttft_prefill_p95_ms", "step_prefill_token_pct",
        "batch_rows_pct", "kv_pool_used_pct", "moe_expert_dev_pct",
        "moe_expert_roofline", "sampler_short_step_pct",
        "serve_attn_proj_dev_pct", "serve_mlp_dev_pct",
        "serve_moe_glue_dev_pct", "serve_head_dev_pct",
        "serve_unscoped_dev_pct"} <= mine
    # readers whose floor is every layer's KV, or another pool's counts
    assert not mine & {"ragged_attn_roofline", "ragged_attn_kv_reread_x",
                       "ragged_attn_roofline.windowed",
                       "kv_held_bytes_per_token", "latent_attn_dev_pct",
                       "latent_attn_roofline", "latent_kv_bytes_per_token"}
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert {"ttft_p95_ms", "itl_p95_ms", "serve_tok_s", "setup_s"} <= e2e
    mix = _json("benchmark", "traffic", "long-chat.json")
    engine = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")["engine"]
    assert engine["max_len"] == mix["prompt_len"]["max"] \
        + mix["answer_len"]["max"]
    assert engine["prefix_caching"] is False


def test_counts_of_the_published_widths():
    cfg = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    assert linear_costs.linear_layers(cfg) == 9
    assert linear_costs.state_bytes(cfg) == 32 * 128 * 128 * 4 == 2_097_152
    assert linear_costs.token_bytes(cfg) == 4 * (2 * 2048 + 2 * 4096 + 64)
    assert linear_costs.token_flops(cfg) == 32 * 8 * 128 * 128
    chip = peaks.PEAKS["TPU v5 lite"]
    # a decode step of 64 rows: the states bound it, 2.4 GB over 9 layers
    rows, tokens = 9 * 64, 9 * 64
    got = linear_costs.recurrence_floor_s(cfg, chip, rows, tokens)
    assert got == pytest.approx(
        (rows * 2 * 2_097_152 + tokens * 49_408) / 819e9)
    assert 2.9e-3 < got < 3.0e-3
    # a step with a 256-token chunk beside them: still the bytes
    tokens = 9 * (64 + 256)
    assert linear_costs.recurrence_floor_s(cfg, chip, rows, tokens) \
        > tokens * 32 * 8 * 128 * 128 / 197e12


def _step(i, t0_ms, **attrs):
    return (i, None, "serve.step", int(t0_ms * 1e6), int((t0_ms + 50) * 1e6),
            attrs)


def test_linear_attn_roofline_over_the_traced_steps(monkeypatch):
    """Two traced steps of nine kernel events each: the floor is the mean
    step's rows and tokens over the peaks, the time the kernel's seconds
    a step."""
    from benchmark import program_log
    reader = _reader("linear_attn_roofline")
    cfg = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    records = [_step(i + 1, 3000 + 60 * i, linear_rows=9 * r,
                     linear_tokens=9 * t)
               for i, (r, t) in enumerate([(60, 60), (64, 320)])]
    w = program_log.cut(records, [], 2, "serve.step")
    w.lo_ns = 0                      # the window opened 3 s before them
    monkeypatch.setattr(program_log, "window", lambda run, name: w)
    trace = {"op_seconds": {f"ragged_gated_delta.{i}": 0.0005
                            for i in range(18)},
             "op_counts": {f"ragged_gated_delta.{i}": 1 for i in range(18)}}
    chip = peaks.PEAKS["TPU v5 lite"]
    got = reader.read({"config": cfg, "trace": trace, "peaks": chip,
                       "traffic": {"trace_after_s": 3},
                       "step_s": [0.05] * 2})
    floor = linear_costs.recurrence_floor_s(cfg, chip, 9 * 62, 9 * 190)
    assert got == pytest.approx(100 * floor / 0.0045)
    assert 0 < got < 100


def test_the_new_readers_find_nothing_on_another_configuration():
    for name in NEW_METRICS:
        assert _reader(name).read(
            {"config": {"hidden_size": 64}, "trace": None, "peaks": None,
             "step_s": []}) is None


def test_linear_attn_dev_pct_reads_the_kernels_events():
    reader = _reader("linear_attn_dev_pct")
    trace = {"busy_s": 2.0, "op_seconds": {"ragged_gated_delta.3": 0.3,
                                           "ragged_gated_delta.9": 0.2,
                                           "fusion.1": 1.5}}
    assert reader.read({"trace": trace}) == pytest.approx(25.0)
    assert reader.read({"trace": dict(trace, op_seconds={"fusion.1": 2.0})}) \
        is None


def test_a_rehearsal_of_the_cell_at_a_small_size(tmp_path):
    """The real configuration's keys at small widths, the real traffic
    file's shape at small lengths, the real metric entries: the path the
    chip run takes, through ``run.py``, with ``correct`` decided by the
    reference. A rehearsal names what it would report and gives no
    value; the trace's device metrics have nothing to read on a CPU."""
    cfg = _json("benchmark", "configs", "qwen3-next-80b-a3b.json")
    cfg.update(hidden_size=64, moe_intermediate_size=32,
               shared_expert_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, linear_num_key_heads=2,
               linear_num_value_heads=4, linear_key_head_dim=16,
               linear_value_head_dim=16, num_hidden_layers=4,
               mlp_layer_types=["sparse"] * 4, num_experts=4,
               router_width=16, expert_offset=4, num_experts_per_tok=4,
               vocab_size=64, dtype="float32",
               logit_tol={"mean": 1e-4, "max": 1e-3},
               engine={"max_len": 128, "max_num_seqs": 4, "page_size": 4,
                       "chunk_size": 16, "q_block": 4,
                       "prefix_caching": False, "num_pages": 128})
    mix = _json("benchmark", "traffic", "long-chat.json")
    mix.update(rate_rps=2.0, warm_s=1, trace_after_s=0.5, trace_s=1,
               prompt_len=dict(mix["prompt_len"], median=40, min=12, max=100),
               answer_len=dict(mix["answer_len"], median=6, min=3, max=10))
    real = _json("BENCHMARK.json")
    bench = dict(real, paths=["data"], configs=[{
        "name": "small", "source": cfg["source"],
        "file": "data/configs/small.json", "reduced": [], "why": "a test"}],
        workloads=[{"name": "small.long-chat", "config": "small",
                    "traffic": "long-chat", "chips": 1, "why": "a test"}])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=["small.long-chat"])
                        for m in real[group]
                        if CELL in m.get("workloads", [CELL])]
    os.makedirs(tmp_path / "data" / "configs")
    os.makedirs(tmp_path / "data" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "data" / "configs" / "small.json").write_text(
        json.dumps(cfg))
    (tmp_path / "data" / "traffic" / "long-chat.json").write_text(
        json.dumps(mix))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--benchmark-json", str(tmp_path / "BENCHMARK.json"),
         "--workload", "small.long-chat", "--seed", "3000000029",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "small.long-chat"
    assert last["correct"] is True, p.stderr[-1500:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"kv_pool_used_pct", "batch_rows_pct", "tokens_per_dispatch",
            "serve_wait_ms_p50"} <= set(last["would_report"])


def test_serve_linear_dev_pct_reads_the_linear_phases_only(monkeypatch):
    """The three ``linear.*`` phases' share of busy; silent where the
    step has no linear layer (a Llama or routed model's table)."""
    from benchmark import device_phases
    reader = _reader("serve_linear_dev_pct")
    run = {"trace": {"busy_s": 0.1}}
    mixed = {("linear.proj", "fwd"): 0.02, ("linear.scan", "fwd"): 0.01,
             ("linear.out", "fwd"): 0.005, ("mlp", "fwd"): 0.05}
    monkeypatch.setattr(device_phases, "charges", lambda run, name: mixed)
    assert reader.read(run) == pytest.approx(35.0)
    plain = {("mlp", "fwd"): 0.05, ("attn.core", "fwd"): 0.04}
    monkeypatch.setattr(device_phases, "charges", lambda run, name: plain)
    assert reader.read(run) is None
