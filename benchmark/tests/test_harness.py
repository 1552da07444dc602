"""The harness end to end on the CPU, and BENCHMARK.json against the
files it names."""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmark", "run.py")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN, *args], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)


MODEL = {"class": "paddle_tpu.models.LlamaForCausalLM",
         "config_class": "paddle_tpu.models.LlamaConfig"}
WIDTHS = {"hidden_act": "silu", "hidden_size": 128, "intermediate_size": 256,
          "max_position_embeddings": 256, "num_attention_heads": 4,
          "num_key_value_heads": 2, "num_hidden_layers": 2,
          "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": 512,
          "model": MODEL, "reference": "llama_dense", "source": "a test"}
NEW_CELLS = {
    "serve": (
        dict(WIDTHS, tie_word_embeddings=False, dtype="float32",
             logit_tol={"mean": 1e-4, "max": 1e-3},
             engine={"max_len": 128, "max_num_seqs": 4, "page_size": 16}),
        {"kind": "serve_open_loop", "arrivals": {"process": "gamma", "cv": 2},
         "rate_rps": 3.0, "set_seed": 1, "warm_s": 1, "trace_after_s": 0.5,
         "trace_s": 1,
         "prompt_len": {"dist": "uniform", "min": 8, "max": 48},
         "answer_len": {"dist": "fixed", "value": 5, "min": 5, "max": 5}},
        {"ttft_p95_ms", "itl_p95_ms", "serve_tok_s", "setup_s"},
        {"tokens_per_dispatch", "engine_step_p50_ms"}),
    "train": (
        dict(WIDTHS, tie_word_embeddings=True, dtype="float32",
             train={"optimizer": "AdamW", "lr": 1e-3, "autocast": "O1",
                    "remat": True, "loss_chunk_size": 32,
                    "loss_rtol": 0.01}),
        {"kind": "train_steps", "batch": 2, "seq": 64, "sharding": None,
         "trace_after_s": 0.5, "trace_s": 1},
        {"train_tok_s", "setup_s"},
        {"train_step_p50_ms"}),
}


COMPARED = {"serve": ["margin_mean", "margin_max", "compiled_in_window"],
            "train": ["loss_rel_err", "loss_third_less_first",
                      "nonfinite_losses"]}


@pytest.mark.parametrize("kind", sorted(NEW_CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_a_cell_made_only_of_new_files_runs(tmp_path, kind, trace):
    """A later PR adds a cell as a configuration file, a traffic file and
    entries: nothing that is there is edited. The metric entries are the
    real file's own, pointed at the new cell."""
    config, mix, e2e, layers = NEW_CELLS[kind]
    cell = f"new-{kind}.new-mix"
    real = _bench()
    bench = dict(real, paths=["data"], configs=[{
        "name": f"new-{kind}", "source": "a test",
        "file": "data/configs/new.json", "reduced": [], "why": "a test"}],
        workloads=[{"name": cell, "config": f"new-{kind}",
                    "traffic": "new-mix", "chips": 1, "why": "a test"}])
    for group in ("end_to_end", "per_layer"):
        bench[group] = [dict(m, workloads=[cell]) for m in real[group]]
    os.makedirs(tmp_path / "data" / "configs")
    os.makedirs(tmp_path / "data" / "traffic")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "data" / "configs" / "new.json").write_text(json.dumps(config))
    (tmp_path / "data" / "traffic" / "new-mix.json").write_text(
        json.dumps(mix))
    p = _run("--benchmark-json", str(tmp_path / "BENCHMARK.json"),
             "--workload", cell, "--seed", "3000000001", "--seconds", "3",
             "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    # a rehearsal names what it would report and gives no value
    assert "metrics" not in last and "device" not in last
    assert last["rehearsal"] == cell and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert (layers if trace else e2e) <= set(last["would_report"])
    # each number that decided ``correct`` beside its limit, as the last
    # lines of the errors
    names = COMPARED[kind]
    assert last["compared"] == sorted(names)
    tail = p.stderr.strip().splitlines()[-len(names) - 1:]
    assert tail[-1] == "correct: True"
    for line, name in zip(tail, names):
        assert line.startswith(f"compared {name}: ") and " limit " in line


def test_without_an_accelerator_no_result_line():
    cell = _bench()["workloads"][0]["name"]
    p = _run("--workload", cell, "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and '"correct"' not in p.stdout


def test_unknown_workload_is_refused():
    p = _run("--workload", "no-such.cell", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0 and "no-such.cell" in p.stderr


# --- BENCHMARK.json against the files it names ------------------------------

def test_every_cell_has_its_files_and_its_metrics():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for cell in b["workloads"]:
        entry = configs[cell["config"]]
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        for key in entry["reduced"]:
            assert cfg[key] == cfg["reduced"][key]["run"]
        assert "assumed" in cfg and "stands_for" in cfg
        mix = os.path.join(ROOT, "benchmark", "traffic",
                           cell["traffic"] + ".json")
        with open(mix) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "runners",
                                           kind + ".py"))
        mine = [m for m in b["end_to_end"]
                if cell["name"] in m.get("workloads", [cell["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        layer = [m for m in b["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
        assert layer
        for m in layer:       # the metric it moves is reported in this cell
            assert cell["name"] in e2e[m["moves"]].get(
                "workloads", [cell["name"]])


@pytest.mark.parametrize("metric", [m["name"] for m in _bench()["per_layer"]])
def test_each_per_layer_metric_has_a_reader_that_agrees(metric):
    entry = next(m for m in _bench()["per_layer"] if m["name"] == metric)
    path = os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["source"], entry["moves"])
    # a reader that finds nothing to read returns nothing
    assert mod.read({"end_to_end": {}, "trace": None, "peaks": None}) is None
