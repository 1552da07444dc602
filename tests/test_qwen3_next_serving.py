"""Qwen3-Next's layers through the serving engine at a small size: linear
(Gated DeltaNet) layers whose recurrent state lives in slots of the page
pool beside the full layers' pages, gated full attention with rotary on
part of each head, zero-centred norms, a softmax router and a gated
shared expert — against the benchmark's plain reference
(``benchmark/references/qwen3_next.py``, float32 ``highest``, no kernel,
cache or batching, the recurrence token by token).

Sizes: hidden 64, layers L L L F, linear heads 2 key / 4 value of 16,
full heads 4 q / 2 kv of 32 with rotary on 8 dims, 16 experts top-4 of
which 8 held, vocabulary 64, page 4, chunks of 16. One engine serves
most tests here (its one compile is most of this file's time)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import qwen3_next_controls as controls
from benchmark.references import qwen3_next as reference
from paddle_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextForCausalLM
from paddle_tpu.profiler import spans
from paddle_tpu.serving import LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 4, 16
SIZES = dict(vocab_size=64, hidden_size=64, moe_intermediate_size=32,
             shared_expert_intermediate_size=32, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             linear_num_key_heads=2, linear_num_value_heads=4,
             linear_key_head_dim=16, linear_value_head_dim=16,
             num_experts=8, router_width=16, expert_offset=4,
             num_experts_per_tok=4, initializer_range=0.08, dtype="float32")
ENGINE = dict(max_len=128, page_size=PAGE, max_num_seqs=4, chunk_size=CHUNK,
              q_block=4, prefix_caching=False)


def _file_cfg(config, **change):
    """The keys the reference reads from a configuration file."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "partial_rotary_factor",
            "full_attention_interval", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "num_experts_per_tok", "norm_topk_prob",
            "expert_offset")
    return dict({k: getattr(config, k) for k in keys}, **change)


@pytest.fixture(scope="module")
def served():
    paddle.seed(11)
    config = Qwen3NextConfig(**SIZES)
    model = Qwen3NextForCausalLM(config).eval()
    return model, config, LLMEngine(model, **ENGINE)


def _worst(w, cfg, prompts, outs, ref=reference):
    worst = 0.0
    for p, got in zip(prompts, outs):
        rows = [len(p) - 1 + j for j in range(len(got))]
        lg = ref.logits_at(w, cfg, p + got, rows)
        worst = max(worst, max(ref.margins(lg, got)))
    return worst


@pytest.fixture(scope="module")
def generated(served):
    """Prompts of 37 (three chunks, the last one mid-block), 5 and 21
    tokens, 12 answers each, decoded through the slots and the pages."""
    model, config, engine = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n).tolist() for n in (37, 5, 21)]
    rids = [engine.add_request(p, max_new_tokens=12) for p in prompts]
    outs = engine.run(max_steps=200)
    return prompts, [outs[r].token_ids for r in rids], rids


def test_layer_kinds_are_static_data_of_the_config():
    kinds = Qwen3NextConfig(**SIZES).layer_kinds()
    assert [k.linear for k in kinds] == [True, True, True, False]
    full = kinds[3]
    assert (full.gate, full.rotary_dim, full.qk_norm, full.window) \
        == (True, 8, True, None)
    assert all(k.mlp == "sparse" for k in kinds)


def test_engine_tokens_sit_on_the_reference_logits(served, generated):
    """Prefill in chunks of 16 (a 37-token prompt over three steps, its
    state and convolution inputs carried in its slot), then decode:
    every token the engine emits is the reference's best at its
    position. Tolerance 1e-4 on the distance below the reference's best
    logit: both sides are float32 at ``highest`` (conftest), so they
    differ by accumulation order alone, 1e-6 of logits of size 1; each
    planted fault below moves a logit by 1e-2 and more."""
    model, config, engine = served
    prompts, outs, rids = generated
    assert engine.decode_cache_size() == 1           # one executable
    assert all(len(o) == 12 for o in outs)
    w, cfg = reference.weights(model), _file_cfg(config)
    assert _worst(w, cfg, prompts, outs) <= 1e-4
    # the step log carries the linear layers' counts
    steps = spans.records("serve.step")[-4:]
    for key in ("linear_rows", "linear_tokens", "state_slots_used",
                "state_slots"):
        assert all(key in r.attrs for r in steps), key
    last = steps[-1].attrs
    assert last["linear_tokens"] == 3 * (last["prefill_tokens"]
                                         + last["decode_tokens"])
    assert last["state_slots"] == 4


def _tampered(monkeypatch, name, value):
    monkeypatch.setattr(reference, name, value)
    jax.clear_caches()                  # the jitted bodies trace it again


@pytest.mark.parametrize("fault", ["gate", "rotary", "decay", "l2norm"])
def test_each_planted_fault_fails_the_comparison(served, generated,
                                                 monkeypatch, fault):
    """The engine's tokens judged by a reference with one fault planted:
    attention's gate dropped, rotary on the whole head, ``g = 0``, q and
    k not L2-normalised. Each misses by far more than the tolerance."""
    model, config, _ = served
    prompts, outs, _ = generated
    w, cfg = reference.weights(model), _file_cfg(config)
    if fault == "gate":
        _tampered(monkeypatch, "_gate", lambda x: 1.0)
    elif fault == "rotary":
        cfg = dict(cfg, partial_rotary_factor=1.0)
    elif fault == "decay":
        w = dict(w, layers=[dict(lw, A_log=jnp.full_like(lw["A_log"], -1e4))
                            if "A_log" in lw else lw for lw in w["layers"]])
    else:
        _tampered(monkeypatch, "_unit", lambda y: y)
    try:
        assert _worst(w, cfg, prompts[:1], outs[:1]) > 1e-2
    finally:
        monkeypatch.undo()
        jax.clear_caches()


@pytest.mark.parametrize("control", controls.REFERENCE)
def test_the_benchmarks_controls_move_the_reference(served, generated,
                                                    control):
    """``benchmark/qwen3_next_controls.py``'s tampered references, which
    judge a chip window's tokens, take effect: each moves the logits at
    the engine's positions by more than ten times what the sound
    comparison allows (int8 weights least: a part in 250 of a column's
    largest weight), and leaves the plain reference as it was."""
    model, config, _ = served
    prompts, outs, _ = generated
    cfg = _file_cfg(config)
    seq = prompts[0] + outs[0]
    rows = [len(prompts[0]) - 1 + j for j in range(len(outs[0]))]
    ref = controls.tampered_reference({"reference": "qwen3_next"}, control)
    moved = ref.logits_at(ref.weights(model), cfg, seq, rows)
    plain = reference.logits_at(reference.weights(model), cfg, seq, rows)
    assert float(jnp.abs(moved - plain).max()) > 1e-3
    assert _worst(reference.weights(model), cfg, prompts, outs) <= 1e-4


def test_the_int8_control_rounds_each_output_column_alone():
    w = jnp.asarray(np.random.default_rng(3).standard_normal((2, 64, 5)),
                    jnp.float32) * jnp.asarray([1.0, 10.0, 1e-3, 0.0, 2.0])
    got = controls.int8(w)
    step = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127
    assert bool(jnp.all(jnp.abs(got - w) <= step / 2 + 1e-7))
    assert bool(jnp.all(got[..., 3] == 0))
    assert int(jnp.unique(jnp.round(got[0, :, 1] / step[0, 0, 1])).size) > 20


def test_slots_and_pages_through_preemption_cancel_and_finish(served):
    """A row preempted mid-answer and recomputed from position 0 (its
    slot returned, a fresh one claimed, the state rebuilt) emits the
    tokens of an unpreempted run of the same prompt; a cancel and every
    finish return each slot and page; the pool's audit holds at every
    step."""
    _, _, engine = served
    pool = engine.pool
    rng = np.random.default_rng(7)
    prompt_a = rng.integers(0, 64, 30).tolist()
    prompt_b = rng.integers(0, 64, 25).tolist()
    solo = engine.add_request(prompt_a, max_new_tokens=20)
    want = engine.run(max_steps=200)[solo].token_ids
    engine.release(solo)
    a = engine.add_request(prompt_a, max_new_tokens=20)
    b = engine.add_request(prompt_b, max_new_tokens=30)
    preempted = False
    while engine.has_unfinished():
        engine.step()
        pool.check_invariants()
        live = [r for r in (a, b) if r in pool]
        assert pool.state_slots_used == len(live)
        assert len({pool.slot_of(r) for r in live}) == len(live)
        if not preempted and len(engine.outputs()[a].token_ids) == 8:
            engine.scheduler.preempt(engine._seqs[a])   # recompute path
            preempted = True
        if len(engine.outputs()[b].token_ids) == 10:
            engine.cancel(b)
    assert engine.outputs()[a].num_preemptions == 1
    assert engine.outputs()[a].token_ids == want
    assert engine.outputs()[b].status == "cancelled"
    assert pool.free_pages == pool.capacity and pool.state_slots_used == 0
    pool.check_invariants()
    assert engine.decode_cache_size() == 1


def test_what_needs_an_earlier_state_is_refused_by_name(served):
    _, _, engine = served
    pool = engine.pool
    pool.allocate("x", 8)
    pool.prepare_append("x", 8)
    try:
        with pytest.raises(ValueError, match="recurrent state at 8 only"):
            pool.rollback("x", 5)
        pool.rollback("x", 8)                        # no change is fine
        with pytest.raises(ValueError, match="recurrent state in slots"):
            pool.fork("y", "x", 4)
    finally:
        pool.free("x")
    pool.check_invariants()
    with pytest.raises(ValueError, match="extract_request"):
        engine.extract_request("x")
    with pytest.raises(ValueError, match="inject_request"):
        engine.inject_request({})


@pytest.mark.parametrize("mode,match", [
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype int8"),
    (dict(quantized_mode="weight_only_int8"), "quantized_mode"),
    (dict(prefill_megakernel="fused"), "prefill_megakernel='fused'"),
    (dict(megakernel_scope="model"), "megakernel_scope='model'"),
    (dict(burst_tokens=4), "burst_tokens > 1"),
    (dict(draft_model=object()), "draft_model"),
    (dict(adapter_slots=2), "adapter_slots"),
    (dict(mesh=object()), "mesh"),
    (dict(host_kv_pages=8), "host_kv_pages"),
    (dict(prefix_caching=True), "with linear layers"),
    (dict(pinned_prefix_pages=4), "with linear layers"),
])
def test_engine_refuses_what_the_linear_kind_does_not_carry(served, mode,
                                                            match):
    model, _, _ = served
    with pytest.raises(ValueError, match=match):
        LLMEngine(model, **dict(ENGINE, **mode))


# ---------------------------------------------------------------------------
# the routed layer alone: softmax router, gated shared expert, shares
# ---------------------------------------------------------------------------

def _rows(n, seed):
    """Rows as a layer's RMSNorm leaves them (mean square 1)."""
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((n, 64)),
                    jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


def _share(held, first, seed=21):
    paddle.seed(seed)
    cfg = Qwen3NextConfig(**dict(SIZES, num_experts=held,
                                 expert_offset=first, router_width=16))
    return cfg, Qwen3NextForCausalLM(cfg)


def test_the_shares_add_up_to_the_whole_layer():
    """Over four shards of 4 experts, the routed parts plus the gated
    shared expert counted once equal the uncut reference layer."""
    from paddle_tpu.serving.spec_decode import _routed_mlp
    whole_cfg, whole = _share(16, 0)
    lyr = whole.serving_params()["layers"][0]
    ref_w = reference.weights(whole)["layers"][0]
    x = _rows(24, 2)
    cfg = _file_cfg(whole_cfg, rms_norm_eps=0.0)
    want = np.asarray(reference._feed_forward(
        dict(ref_w, ln2=jnp.zeros((64,))), cfg, x) - x)
    shared = np.asarray(reference._swiglu(
        ref_w["gate"], ref_w["up"], ref_w["down"], x,
        reference._gates(ref_w["router"], ref_w["shared_gate"], x,
                         top_k=4, norm=True)[1]))
    live = jnp.ones((24,), bool)
    total, pairs = shared.copy(), 0
    for s in range(4):
        lo = 4 * s
        part = dict(lyr, **{k: lyr[k][lo:lo + 4] for k in
                            ("experts_gate", "experts_up", "experts_down")})
        part_cfg = dataclasses.replace(whole_cfg, num_experts=4,
                                       expert_offset=lo)
        stats = []
        y = np.asarray(_routed_mlp(part, x[None], live, part_cfg, True,
                                   stats)[0])
        total += y - shared                       # its routed part alone
        pairs += int(stats[0][0])
    assert pairs == 24 * 4                        # every pair landed once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# check_published, the lazy import, the other kinds untouched
# ---------------------------------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        return json.load(f)


def test_the_benchmarks_file_passes_and_builds_the_share():
    cfg = _published()
    Qwen3NextConfig.check_published(cfg)
    fields = {f.name for f in dataclasses.fields(Qwen3NextConfig)}
    config = Qwen3NextConfig(**{k: v for k, v in cfg.items() if k in fields})
    assert (config.router_width, config.num_experts,
            config.num_experts_per_tok) == (512, 32, 10)
    assert (config.hidden_size, config.num_attention_heads,
            config.num_key_value_heads, config.head_dim,
            config.rotary_dim) == (2048, 16, 2, 256, 64)
    assert (config.linear_num_key_heads, config.linear_num_value_heads,
            config.linear_key_head_dim, config.linear_value_head_dim) \
        == (16, 32, 128, 128)
    kinds = config.layer_kinds()
    assert [k.linear for k in kinds] == [True, True, True, False] * 3


@pytest.mark.parametrize("change,match", [
    (dict(use_sliding_window=True), "use_sliding_window"),
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(linear_num_value_heads=24), "linear_num_value_heads"),
    (dict(linear_conv_kernel_dim=1), "linear_conv_kernel_dim"),
    (dict(full_attention_interval=0), "full_attention_interval"),
    (dict(layer_types=["full_attention"] * 12), "layer_types"),
    (dict(mlp_layer_types=["dense"] * 12), "mlp_layer_types"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
])
def test_check_published_refuses_by_name(change, match):
    cfg = dict(_published(), **change)
    with pytest.raises(ValueError, match=match):
        Qwen3NextConfig.check_published(cfg)


def test_the_model_module_is_imported_only_when_named():
    code = ("import sys, paddle_tpu, paddle_tpu.models, paddle_tpu.serving;"
            "import benchmark.build;"
            "print('paddle_tpu.models.qwen3_next' in sys.modules,"
            " 'paddle_tpu.kernels.gated_delta' in sys.modules)")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == ["False", "False"]


def test_an_engine_of_the_existing_kinds_takes_no_new_operand():
    """A Llama engine's control buffer has no ``slots`` field, its pool
    no state layer, and it compiles one executable in which no
    instruction belongs to a linear layer's phases."""
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
    paddle.seed(3)
    model = LlamaForCausalLM(llama_tiny_config()).eval()
    engine = LLMEngine(model, max_len=64, page_size=4, max_num_seqs=2,
                       prefix_caching=False)
    assert "slots" not in engine._operands.fields
    assert engine.pool.state_layers == ()
    engine.add_request([1, 2, 3, 4, 5], max_new_tokens=3)
    engine.run(max_steps=50)
    assert engine.decode_cache_size() == 1
    from paddle_tpu.profiler import phases
    table = phases.table(phases.newest("serve.step"))
    assert table and not any(p and p.startswith("linear.")
                             for p, _ in table.values())
    last = spans.records("serve.step")[-1].attrs
    assert "linear_rows" not in last and "state_slots" not in last
