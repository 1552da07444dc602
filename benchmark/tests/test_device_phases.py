"""``benchmark/device_phases.py``: the join of the program's instruction
-> phase table with a traced window's per-instruction seconds, and the
eleven ``*_dev_pct`` readers on top of it."""
import importlib.util
import json
import os

import pytest

from benchmark import device_phases, run, xplane

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

HLO = """HloModule jit_ragged_step

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(ragged_step)/phase.attn.qkv/dot_general"}
  %copy.2 = f32[4]{0} copy(%fusion.1), metadata={op_name="jit(ragged_step)/phase.attn.out/transpose"}
  %fusion.3 = f32[4]{0} fusion(%copy.2), kind=kLoop, calls=%g, metadata={op_name="jit(ragged_step)/phase.mlp/dot_general"}
  %sort.4 = f32[4]{0} sort(%fusion.3), dimensions={0}, to_apply=%lt, metadata={op_name="jit(ragged_step)/phase.moe.dispatch/sort"}
  %while.5 = f32[4]{0} while(%sort.4), condition=%c, body=%b, metadata={op_name="jit(ragged_step)/phase.sample/while"}
  %fusion.6 = f32[4]{0} fusion(%while.5), kind=kLoop, calls=%h, metadata={op_name="jit(ragged_step)/phase.head/dot_general"}
  ROOT %fusion.7 = f32[4]{0} fusion(%fusion.6), kind=kLoop, calls=%i, metadata={op_name="jit(ragged_step)/concatenate"}
}
"""
SECONDS = {"fusion.1": 1.0, "copy.2": 0.5, "fusion.3": 4.0, "sort.4": 0.25,
           "while.5": 3.0, "fusion.6": 1.0, "fusion.7": 0.25,
           "other_executable.9": 0.5}
BUSY = 8.0


class _Compiled:
    """What the registry keeps of a ``jax.stages.Compiled``: its text."""

    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def _reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def phases():
    from paddle_tpu.profiler import phases
    phases.clear()
    yield phases
    phases.clear()


def _run(seconds=SECONDS, busy=BUSY):
    return {"trace": {"op_seconds": dict(seconds), "busy_s": busy}}


SERVE = ("serve_attn_proj_dev_pct", "serve_mlp_dev_pct",
         "serve_moe_glue_dev_pct", "serve_head_dev_pct",
         "serve_unscoped_dev_pct")
TRAIN = ("train_attn_dev_pct", "train_mlp_dev_pct", "train_loss_dev_pct",
         "train_optimizer_dev_pct", "train_remat_dev_pct",
         "train_unscoped_dev_pct")


def test_the_shares_are_seconds_over_busy_and_add_up(phases):
    phases.register("serve.step", _Compiled(HLO))
    got = {name: _reader(name).read(_run()) for name in SERVE}
    assert got == {
        "serve_attn_proj_dev_pct": 100 * 1.5 / BUSY,
        "serve_mlp_dev_pct": 100 * 4.0 / BUSY,
        "serve_moe_glue_dev_pct": 100 * 0.25 / BUSY,
        "serve_head_dev_pct": 100 * 1.0 / BUSY,    # the while: a container
        # no phase in its metadata, and a name the table does not know
        "serve_unscoped_dev_pct": 100 * 0.75 / BUSY}
    assert sum(got.values()) <= 100.0
    # the train readers ask for another executable
    assert all(_reader(name).read(_run()) is None for name in TRAIN)


def test_passes_cut_across_the_phases(phases):
    phases.register("train.step", _Compiled(HLO.replace(
        "phase.mlp/dot_general",
        "rematted_computation/phase.mlp/jvp()/dot_general").replace(
        "phase.attn.qkv/dot_general",
        "phase.attn.qkv/transpose(jvp())/dot_general")))
    run = _run()
    assert _reader("train_remat_dev_pct").read(run) == 100 * 4.0 / BUSY
    assert _reader("train_mlp_dev_pct").read(run) == 100 * 4.0 / BUSY
    assert _reader("train_attn_dev_pct").read(run) == 100 * 1.5 / BUSY
    charged = device_phases.charges(run, "train.step")
    assert charged[("attn.qkv", "bwd")] == 1.0
    assert device_phases.share(charged, BUSY, passes=("bwd",)) == 12.5
    assert all(_reader(name).read(run) is None for name in SERVE)


def test_the_newest_engine_of_a_process_is_read(phases):
    phases.register("serve.step:0",
                    _Compiled(HLO.replace("phase.mlp", "phase.head")))
    phases.register("serve.step:1", _Compiled(HLO))
    assert _reader("serve_mlp_dev_pct").read(_run()) == 50.0


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_nothing_to_read_is_none(name, phases):
    read = _reader(name).read
    assert read(_run()) is None                     # nothing registered
    exe = "serve.step" if name in SERVE else "train.step"
    # an executable from a cache another tree filled: no phase at all
    phases.register(exe, _Compiled(HLO.replace("phase.", "scope.")))
    assert read(_run()) is None
    phases.register(exe, _Compiled(HLO))
    assert read({"trace": None}) is None            # an untraced run
    assert read({"end_to_end": {}}) is None
    assert read(_run(busy=0.0)) is None
    assert read(_run()) is not None


# --- a rehearsal names the eleven -------------------------------------------

MODEL = {"class": "paddle_tpu.models.LlamaForCausalLM",
         "config_class": "paddle_tpu.models.LlamaConfig"}
WIDTHS = {"hidden_act": "silu", "hidden_size": 128, "intermediate_size": 256,
          "max_position_embeddings": 256, "num_attention_heads": 4,
          "num_key_value_heads": 2, "num_hidden_layers": 2,
          "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "vocab_size": 512,
          "model": MODEL, "reference": "llama_dense", "source": "a test"}
CELLS = {
    "serve": (
        dict(WIDTHS, tie_word_embeddings=False, dtype="float32",
             logit_tol={"mean": 1e-4, "max": 1e-3},
             engine={"max_len": 128, "max_num_seqs": 4, "page_size": 16}),
        {"kind": "serve_open_loop", "arrivals": {"process": "gamma", "cv": 2},
         "rate_rps": 3.0, "set_seed": 1, "warm_s": 1, "trace_after_s": 0.5,
         "trace_s": 1,
         "prompt_len": {"dist": "uniform", "min": 8, "max": 48},
         "answer_len": {"dist": "fixed", "value": 5, "min": 5, "max": 5}},
        SERVE, "serve.step"),
    "train": (
        dict(WIDTHS, tie_word_embeddings=True, dtype="float32",
             train={"optimizer": "AdamW", "lr": 1e-3, "autocast": "O1",
                    "remat": True, "loss_chunk_size": 32,
                    "loss_rtol": 0.01}),
        {"kind": "train_steps", "batch": 2, "seq": 64, "sharding": None,
         "trace_after_s": 0.5, "trace_s": 1},
        TRAIN, "train.step"),
}


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_a_rehearsal_names_the_readers_of_its_kind(kind, tmp_path, capsys,
                                                   monkeypatch, phases):
    """A cell of files this test writes, walked by ``run.py --rehearse
    --trace 1``. A CPU's trace holds no device plane, so the reduction
    is stood in for: every instruction of the step the program
    registered, a millisecond each. The readers then name the metrics."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    # compile here: a cached executable carries its first tree's scopes
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    config, mix, names, executable = CELLS[kind]
    cell = f"new-{kind}.new-mix"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
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

    def reduce(events, **kw):
        table = phases.table(phases.newest(executable))
        seconds = {name: 1e-3 for name in table}
        return {"window_s": 2 * len(seconds) * 1e-3,
                "busy_s": len(seconds) * 1e-3, "idle_pct": 50.0,
                "op_seconds": seconds, "op_counts": dict.fromkeys(seconds, 1),
                "idle_seconds_by_span": {}, "chips": 1}

    monkeypatch.setattr(xplane, "reduce", reduce)
    try:
        rc = run.main(["--benchmark-json", str(tmp_path / "BENCHMARK.json"),
                       "--workload", cell, "--seed", "3000000001",
                       "--seconds", "3", "--trace", "1", "--rehearse"])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["rehearsal"] == cell and last["correct"] is True
    assert "metrics" not in last
    ours = {n for n in last["would_report"] if n.endswith("_dev_pct")
            and n.startswith(("serve_", "train_"))}
    assert ours == set(names)
