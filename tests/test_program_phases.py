"""Device time by phase (ISSUE 39): ``paddle_tpu/profiler/phases.py``.

The layer bodies name their phases (``jax.named_scope("phase.<x>")``),
the owner of a step executable registers its compiled handle at the
first launch, and ``table`` / ``charge`` turn the optimised HLO's
``metadata={op_name=...}`` into *instruction -> (phase, pass)* and a
trace's per-instruction seconds into seconds by phase. Held here, on the
CPU at tiny shapes:

(a) the table over a toy step: forward, ``transpose(`` and recomputed
    instructions, the innermost of nested phases, None for an unscoped
    one, a scanned body and a ``conditional``'s branches;
(b) a Llama, a routed and a latent engine each register ``serve.step``
    at their first launch with every phase of their kind, one trace, and
    ``decode_cache_size()`` / ``decode_compiles`` where they were;
(c) a ``TrainStep`` under the tape's recompute and a chunked loss: the
    backward and the recomputed forward carry the forward's phases;
(d) the scopes are metadata alone: with ``phases.phase`` a null context
    the optimised HLO, metadata stripped, is the same bytes;
(e) the registry outlives its owner and holds no array;
(f) ``charge`` leaves containers out and adds up.
"""
import contextlib
import gc

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit.hlo_forensics import (instruction_metadata,
                                          strip_metadata)
from paddle_tpu.models import (ExaoneMoeConfig, ExaoneMoeForCausalLM,
                               LlamaForCausalLM, llama_tiny_config)
from paddle_tpu.models.deepseek_mla import (DeepseekMlaConfig,
                                            DeepseekMlaForCausalLM)
from paddle_tpu.core import phase_scope
from paddle_tpu.profiler import phases
from paddle_tpu.serving import LLMEngine

#: opcodes that do work on a device (a parameter or a tuple carries no
#: metadata worth counting)
WORK = ("fusion", "dot", "convolution", "custom-call", "copy", "reduce",
        "sort", "scatter", "gather", "dynamic-update-slice")


@pytest.fixture(scope="module", autouse=True)
def _fresh_compiles():
    """JAX's persistent cache leaves metadata out of its key: an
    executable another tree cached would come back with that tree's
    scopes. These tests read scopes, so they compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# --- (a) the table over a toy step ------------------------------------------

def _toy_step(ws, x, flag):
    def layer(h, w):
        with phases.phase("mlp"):
            h = jnp.tanh(h @ w)
            with phases.phase("norm"):          # nested: the innermost
                h = h / jnp.sqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-5)
        return h, None

    def loss(ws, x):
        h, _ = jax.lax.scan(jax.checkpoint(layer, prevent_cse=False), x, ws)
        with phases.phase("loss"):
            return jnp.sum(h * h)

    value, grad = jax.value_and_grad(loss)(ws, x)
    with phases.phase("optimizer"):
        ws = ws - 0.1 * grad

    def yes(a):
        with phases.phase("sample"):
            return jnp.sin(a) * 2

    def no(a):
        with phases.phase("guard"):
            return jnp.cos(a) + 1

    picked = jax.lax.cond(flag[0] > 0, yes, no, x)
    return ws, value, picked + 1.0                  # the add: no phase


@pytest.fixture(scope="module")
def toy():
    step = jax.jit(_toy_step)
    args = (jnp.ones((3, 32, 32)), jnp.ones((4, 32)), np.zeros((2,), np.int32))
    step(*args)
    compiled = step.lower(*phases.launch_specs(args)).compile()
    phases.register("toy.step", compiled)
    by_name = {n: (op, o) for n, op, o, _ in
               instruction_metadata(phases.text("toy.step"))}
    return phases.table("toy.step"), by_name


def _where(by_name, *needles):
    return [n for n, (_, o) in by_name.items()
            if o and all(x in o for x in needles)]


def test_table_labels_forward_backward_and_recomputed(toy):
    table, by_name = toy
    assert set(table) == set(by_name)
    fwd = _where(by_name, "jvp(", "phase.mlp/dot_general")
    fwd = [n for n in fwd if "transpose(" not in by_name[n][1]]
    assert fwd and all(table[n] == ("mlp", "fwd") for n in fwd)
    remat = _where(by_name, "rematted_computation", "phase.mlp/dot_general")
    assert remat and all(table[n] == ("mlp", "remat") for n in remat)
    bwd = [n for n in _where(by_name, "transpose(", "phase.mlp/")
           if "rematted_computation" not in by_name[n][1]
           and "phase.norm" not in by_name[n][1]]
    assert bwd and all(table[n] == ("mlp", "bwd") for n in bwd)
    # every pass and nothing else
    assert {p for _, p in table.values()} == set(phases.PASSES)


def test_table_takes_the_innermost_phase_and_none_without_one(toy):
    table, by_name = toy
    nested = _where(by_name, "phase.mlp/phase.norm/")
    assert nested and all(table[n][0] == "norm" for n in nested)
    plain = [n for n, (op, o) in by_name.items()
             if o and "phase." not in o and op in WORK + ("add",)]
    assert plain and all(table[n][0] is None for n in plain)
    assert phases.classify(None) == (None, "fwd")
    assert phases.classify("jit(f)/phase.nosuch/mul") == (None, "fwd")


def test_table_reads_a_scanned_body_and_a_conditionals_branches(toy):
    table, by_name = toy
    body = _where(by_name, "while/body", "phase.mlp")
    assert body and all(table[n][0] in ("mlp", "norm") for n in body)
    assert any(table[n] == ("sample", "fwd")
               for n in _where(by_name, "branch_1_fun"))
    assert any(table[n] == ("guard", "fwd")
               for n in _where(by_name, "branch_0_fun"))
    ops = {op for op, _ in by_name.values()}
    assert {"while", "conditional", "fusion"} <= ops


def test_a_phase_outside_the_vocabulary_raises_at_trace_time():
    with pytest.raises(ValueError, match="attn.qvk"):
        with phases.phase("attn.qvk"):
            pass
    assert phases.open_phase() is None


# --- (b) the engines --------------------------------------------------------

LLAMA = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
             num_attention_heads=2, num_key_value_heads=2, vocab_size=128)
EXAONE = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=32, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=32,
              sliding_window=8, num_experts=8, num_experts_per_tok=2,
              initializer_range=0.08, dtype="float32")
MLA = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
           moe_intermediate_size=32, num_hidden_layers=2,
           num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           max_position_embeddings=512, first_k_dense_replace=1,
           n_routed_experts=16, num_experts_per_tok=4, n_group=4,
           topk_group=2, initializer_range=0.08, dtype="float32")
DENSE = {"embed", "norm", "attn.qkv", "attn.core", "attn.out", "mlp", "head",
         "guard", "sample"}
ROUTED = DENSE | {"moe.route", "moe.dispatch", "moe.experts", "moe.combine"}


def _engine(kind, **kw):
    if kind == "llama":
        paddle.seed(7)
        return LLMEngine(LlamaForCausalLM(llama_tiny_config(**LLAMA)),
                         max_len=64, page_size=4, max_num_seqs=4, **kw)
    paddle.seed(11)
    if kind == "exaone":
        model = ExaoneMoeForCausalLM(ExaoneMoeConfig(**EXAONE)).eval()
        return LLMEngine(model, max_len=64, page_size=4, max_num_seqs=4,
                         chunk_size=16, q_block=4, prefix_caching=False,
                         **kw)
    model = DeepseekMlaForCausalLM(DeepseekMlaConfig(**MLA)).eval()
    return LLMEngine(model, max_len=64, page_size=4, max_num_seqs=4,
                     chunk_size=16, q_block=4, num_pages=64, **kw)


class _Calls:
    """Counts what runs while a step launches: traces of the step (its
    ``embed`` phase opens once a trace) and ``as_text()`` of any
    compiled handle."""

    def __init__(self, monkeypatch):
        self.traces = self.texts = 0
        real_phase, real_text = phases.phase, jax.stages.Compiled.as_text

        def phase(name):
            self.traces += name == "embed"
            return real_phase(name)

        def as_text(compiled, *a, **kw):
            self.texts += 1
            return real_text(compiled, *a, **kw)

        monkeypatch.setattr(phases, "phase", phase)
        monkeypatch.setattr(jax.stages.Compiled, "as_text", as_text)


@pytest.mark.parametrize("kind,want", [("llama", DENSE), ("exaone", ROUTED),
                                       ("mla", ROUTED)])
def test_an_engines_first_launch_registers_its_step(kind, want, monkeypatch):
    calls = _Calls(monkeypatch)
    phases.clear()
    eng = _engine(kind)
    eng.add_request([1, 2, 3, 4, 5], max_new_tokens=3)
    assert phases.registered() == []
    eng.step()
    # one trace, one compile, and nothing a reader has not asked for
    assert phases.registered() == ["serve.step"]
    assert (calls.traces, calls.texts) == (1, 0)
    assert eng.decode_cache_size() == 1
    assert eng.metrics.decode_compiles.value == 1
    eng.step()
    assert calls.traces == 1 and eng.decode_cache_size() == 1
    table = phases.table("serve.step")
    assert calls.texts == 1
    assert {p for p, _ in table.values()} - {None} == want
    assert {p for _, p in table.values()} == {"fwd"}
    phases.table("serve.step")                      # parsed once a handle
    assert calls.texts == 1


def test_an_engine_tag_keys_the_registry(monkeypatch):
    phases.clear()
    eng = _engine("llama", engine_id=3)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.step()
    assert phases.registered() == ["serve.step:3"]
    assert phases.table() is phases.table("serve.step:3")   # the newest
    assert phases.table("serve.step") is None


# --- (c) the train step: backward and recompute carry the phase -------------

def _train_step(remat=True, capture_hlo=False, sharding=None):
    paddle.seed(5)
    cfg = llama_tiny_config(num_hidden_layers=2, hidden_size=64,
                            intermediate_size=128, num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=128,
                            remat=remat, loss_chunk_size=32,
                            tie_word_embeddings=True)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def loss_fn(ids):
        with paddle.amp.auto_cast(enable=True, level="O1",
                                  dtype="bfloat16"):
            return model(ids, labels=ids)[1]

    step = paddle.jit.TrainStep(model, loss_fn, opt, capture_hlo=capture_hlo,
                                sharding=sharding)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (8, 64)), dtype="int64")
    return step, ids


def test_train_backward_and_recompute_carry_the_forwards_phase(monkeypatch):
    calls = _Calls(monkeypatch)
    phases.clear()
    step, ids = _train_step()
    first = float(step(ids).numpy())
    assert phases.registered() == ["train.step"] and calls.texts == 0
    assert np.isfinite(first) and float(step(ids).numpy()) < first
    table = phases.table("train.step")
    assert calls.texts == 1
    scoped = {"fwd": [0, 0], "bwd": [0, 0], "remat": [0, 0]}
    for name, op, op_name, _ in instruction_metadata(
            phases.text("train.step")):
        if op in WORK and op_name:
            phase, which = table[name]
            scoped[which][0] += phase is not None
            scoped[which][1] += 1
    share = {k: a / max(b, 1) for k, (a, b) in scoped.items()}
    assert scoped["bwd"][1] > 20 and scoped["remat"][1] > 10, scoped
    assert share["bwd"] >= share["fwd"] and share["remat"] >= share["fwd"], \
        scoped
    assert share["bwd"] > 0.9 and share["remat"] > 0.9, scoped
    found = {(p, w) for p, w in table.values() if p is not None}
    for phase in ("attn.qkv", "attn.core", "attn.out", "mlp", "norm"):
        assert {(phase, "fwd"), (phase, "bwd")} <= found
    # the tape's replay (fleet/recompute.py) is marked as jax.checkpoint
    # marks its own; what of it the compiler merges with the first
    # forward (the layer's first products, whose operands are saved)
    # keeps the first forward's name
    assert {("attn.core", "remat"), ("mlp", "remat"),
            ("norm", "remat")} <= found
    # the chunked loss recomputes inside jax.checkpoint, under its phase
    assert {("loss", "fwd"), ("loss", "bwd"), ("loss", "remat"),
            ("optimizer", "fwd"), ("embed", "fwd"), ("head", "fwd")} <= found
    assert ("optimizer", "bwd") not in found


@pytest.mark.parametrize("sharding", [None, "tp=2,dp=4", "dp=8,zero"])
def test_a_train_steps_first_call_traces_lowers_and_compiles_once(
        sharding, monkeypatch):
    """The handle ``TrainStep`` registers after its first call comes out
    of JAX's in-memory caches: through the call and the register
    together the step is traced once, lowered once and compiled once,
    sharded or not (JAX's own events count the last two)."""
    calls = _Calls(monkeypatch)
    built = {"jaxpr_to_mlir_module_duration": 0,
             "backend_compile_duration": 0}

    def listen(event, duration, fun_name=None, **kw):
        stage = event.rsplit("/", 1)[1]
        if stage in built and fun_name == "jit(pure_step)":
            built[stage] += 1

    phases.clear()
    step, ids = _train_step(sharding=sharding)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        step(ids)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert phases.registered() == ["train.step"]
    assert calls.traces == 1
    assert built == {"jaxpr_to_mlir_module_duration": 1,
                     "backend_compile_duration": 1}
    assert (step.last_hlo_text is not None) == (sharding is not None)
    assert calls.texts == (sharding is not None)    # the forensics' text


# --- (d) metadata alone -----------------------------------------------------

def _null_phase(monkeypatch):
    # the layer bodies' name for it and the tape's (core/phase_scope.py)
    for module in (phases, phase_scope):
        monkeypatch.setattr(module, "phase",
                            lambda name: contextlib.nullcontext())


def test_the_engines_step_is_the_same_program_without_the_scopes(
        monkeypatch):
    scoped = _engine("llama").ragged_step_hlo()
    assert "phase.attn.qkv" in scoped
    with monkeypatch.context() as m:
        _null_phase(m)
        bare = _engine("llama").ragged_step_hlo()
    assert "phase." not in bare
    assert strip_metadata(bare) == strip_metadata(scoped)
    assert "metadata=" not in strip_metadata(scoped)


def test_the_train_step_is_the_same_program_without_the_scopes(monkeypatch):
    step, ids = _train_step(capture_hlo=True)
    step(ids)
    scoped = step.last_hlo_text
    assert "phase.optimizer" in scoped and "rematted_computation" in scoped
    with monkeypatch.context() as m:
        _null_phase(m)
        step, ids = _train_step(capture_hlo=True)
        step(ids)
    bare = step.last_hlo_text
    assert "phase." not in bare
    assert strip_metadata(bare) == strip_metadata(scoped)


# --- (e) the registry outlives its owner and holds no array -----------------

def test_the_registry_outlives_its_owner_and_holds_no_array():
    phases.clear()
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    eng = _engine("llama")
    eng.add_request([1, 2, 3, 4], max_new_tokens=2)
    eng.step()
    held = [a for a in jax.live_arrays() if id(a) not in before]
    assert held                                     # weights, pools
    del eng, held
    gc.collect()
    # (the seed the engine's builder set is the process's, two words)
    left = [a for a in jax.live_arrays()
            if id(a) not in before and a.size > 2]
    assert left == [], [(a.shape, a.dtype) for a in left]
    table = phases.table("serve.step")              # still reads
    assert {"attn.qkv", "mlp", "head"} <= {p for p, _ in table.values()}


# --- (f) charge -------------------------------------------------------------

HLO = """HloModule jit_step

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(step)/while/body/phase.mlp/mul"}
}

ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4]{0:T(128)} parameter(0)
  %fusion.1 = f32[4]{0:T(128)} fusion(%a), kind=kLoop, calls=%g, metadata={op_name="jit(step)/phase.attn.qkv/dot_general" source_file="x.py" source_line=3}
  %while.3 = f32[4]{0} while(%fusion.1), condition=%c, body=%body, metadata={op_name="jit(step)/while"}
  %cond.1.clone = f32[4]{0} conditional(%a, %a, %a), branch_computations={%t, %e}
  %copy.7 = f32[4]{0} copy(%while.3), metadata={op_name="jit(step)/add"}
  %copy.8 = f32[4]{0:T(128)} copy(%a)
  %fusion.9 = f32[4]{0} fusion(%copy.8), kind=kLoop, calls=%k, metadata={op_name="jit(step)/phase.head/dot_general"}
  %fusion.4 = (f32[4]{0:T(128)}, f32[4]{0:T(128)}) fusion(%copy.7), kind=kLoop, calls=%h, metadata={op_name="jit(step)/phase.mlp/transpose(jvp())/mul"}
  ROOT %fused_adamw.1 = (f32[4]{0}, f32[4]{0}) custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/phase.optimizer/jit(body)/pallas_call"}
}
"""


class _Compiled:
    """What the registry keeps of a ``jax.stages.Compiled``: its text."""

    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


def test_charge_leaves_containers_out_and_adds_up():
    phases.clear()
    assert phases.charge({"fusion.1": 1.0}) is None     # nothing registered
    phases.register("hand.step", _Compiled(HLO))
    table = phases.table("hand.step")
    assert table["fusion.1"] == ("attn.qkv", "fwd")
    assert table["fusion.2"] == ("mlp", "fwd")          # a loop's body
    assert table["fusion.4"] == ("mlp", "bwd")          # a tuple, a tile
    assert table["fused_adamw.1"] == ("optimizer", "fwd")
    assert table["copy.7"] == (None, "fwd")         # a traced op, no phase
    # no metadata at all: the compiler's own copy goes to its reader,
    # and the table says which instructions it placed so
    assert table["copy.8"] == table["fusion.9"] == ("head", "fwd")
    placed = phases.placed_by_reader("hand.step")
    assert "copy.8" in placed and not placed & {"fusion.9", "copy.7"}
    seconds = {"fusion.1": 1.0, "fusion.2": 2.0, "while.3": 2.5,
               "cond.1.clone": 0.5, "copy.7": 0.25, "fusion.4": 4.0,
               "fused_adamw.1": 8.0, "call.9": 3.0, "elsewhere.5": 0.125}
    got = phases.charge(seconds, "hand.step")
    assert got == {("attn.qkv", "fwd"): 1.0, ("mlp", "fwd"): 2.0,
                   ("mlp", "bwd"): 4.0, ("optimizer", "fwd"): 8.0,
                   (None, "fwd"): 0.375}
    containers = seconds["while.3"] + seconds["cond.1.clone"] \
        + seconds["call.9"]
    assert sum(got.values()) == sum(seconds.values()) - containers
    # the newest handle of a name replaces the older one's table
    phases.register("hand.step",
                    _Compiled(HLO.replace("phase.attn.qkv", "phase.head")))
    assert phases.table("hand.step")["fusion.1"] == ("head", "fwd")


def test_a_module_printed_without_sigils_reads_the_same():
    """``%name`` or ``name``: the table, the containers and what the
    first-reader rule placed are the same."""
    assert phases.parse(HLO.replace("%", "")) == phases.parse(HLO)
    refs = {n: r for n, _, _, r in instruction_metadata(HLO.replace("%", ""))}
    assert refs["fusion.9"] == ("copy.8",) and refs["copy.8"] == ("a",)
    assert refs["cond.1.clone"] == ("a", "a", "a")
