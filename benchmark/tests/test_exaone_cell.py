"""The cell ``k-exaone-236b-a23b.mixed-len``: its files against the
program's config class, a rehearsal of it at a small size through
``run.py`` on the CPU, and the counts its rooflines are taken against."""
import json
import os
import subprocess
import sys

from benchmark import build, moe_costs

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "k-exaone-236b-a23b.mixed-len"
NEW_METRICS = {"moe_expert_dev_pct", "moe_expert_roofline",
               "ragged_attn_roofline.windowed", "kv_held_bytes_per_token"}


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


def test_the_file_is_what_its_config_class_runs():
    cfg = _json("benchmark", "configs", "k-exaone-236b-a23b.json")
    cls = build.load_attr(cfg["model"]["config_class"])
    build.published_check(cls)(cfg)                  # raises on a drop
    assert cfg["router_width"] == 128 and cfg["num_experts_per_tok"] == 8
    assert len(cfg["layer_types"]) == 48             # published, whole
    bench = _json("BENCHMARK.json")
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert NEW_METRICS <= mine
    # window layers undercut these two metrics' floor: kept out
    assert not mine & {"ragged_attn_roofline", "ragged_attn_kv_reread_x"}


def test_counts_of_the_published_widths():
    cfg = _json("benchmark", "configs", "k-exaone-236b-a23b.json")
    assert moe_costs.sparse_layers(cfg) == 7
    assert moe_costs.kv_bytes_per_token_per_layer(cfg) == 4096
    assert moe_costs.page_bytes(cfg) == (2 * 4096 * 16, 6 * 4096 * 16)
    one = 3 * 6144 * 2048 * 2                        # an expert's matrices
    assert moe_costs.expert_bytes(cfg, 1, 0) == one == 75_497_472
    assert moe_costs.expert_bytes(cfg, 0, 1) == 3 * (6144 + 2048) * 2
    assert moe_costs.expert_flops(cfg, 2) == 2 * 6 * 6144 * 2048


def test_a_rehearsal_of_the_cell_at_a_small_size(tmp_path):
    """The real configuration's keys at small widths, the real traffic
    file's shape at small lengths, the real metric entries: the path the
    chip run takes, through ``run.py``, with ``correct`` decided by the
    reference. A rehearsal names what it would report and gives no
    value; the trace's device metrics have nothing to read on a CPU."""
    cfg = _json("benchmark", "configs", "k-exaone-236b-a23b.json")
    cfg.update(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               num_hidden_layers=5, num_experts=4, router_width=8,
               expert_offset=4, num_experts_per_tok=2, vocab_size=64,
               sliding_window=8, dtype="float32",
               sliding_windows=[8 if t == "sliding_attention" else 0
                                for t in cfg["layer_types"]],
               logit_tol={"mean": 1e-4, "max": 1e-3},
               engine={"max_len": 128, "max_num_seqs": 4, "page_size": 4,
                       "chunk_size": 16, "q_block": 4,
                       "prefix_caching": False, "num_pages": 128})
    mix = _json("benchmark", "traffic", "mixed-len.json")
    mix.update(rate_rps=2.0, warm_s=1, trace_after_s=0.5, trace_s=1,
               prompt_len=dict(mix["prompt_len"], median=24, min=6, max=80),
               answer_len=dict(mix["answer_len"], median=6, min=3, max=10))
    real = _json("BENCHMARK.json")
    bench = dict(real, paths=["data"], configs=[{
        "name": "small", "source": cfg["source"],
        "file": "data/configs/small.json", "reduced": [], "why": "a test"}],
        workloads=[{"name": "small.mixed-len", "config": "small",
                    "traffic": "mixed-len", "chips": 1, "why": "a test"}])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=["small.mixed-len"])
                        for m in real[group]
                        if CELL in m.get("workloads", [CELL])]
    os.makedirs(tmp_path / "data" / "configs")
    os.makedirs(tmp_path / "data" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "data" / "configs" / "small.json").write_text(
        json.dumps(cfg))
    (tmp_path / "data" / "traffic" / "mixed-len.json").write_text(
        json.dumps(mix))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--benchmark-json", str(tmp_path / "BENCHMARK.json"),
         "--workload", "small.mixed-len", "--seed", "3000000029",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] == "small.mixed-len"
    assert last["correct"] is True, p.stderr[-1500:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"kv_held_bytes_per_token", "kv_pool_used_pct",
            "batch_rows_pct", "tokens_per_dispatch",
            "serve_wait_ms_p50"} <= set(last["would_report"])
