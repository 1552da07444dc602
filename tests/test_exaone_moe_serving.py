"""K-EXAONE's layer through the serving engine at a small size: window
and full attention layers in one page pool, q/k head norm, rotary on
window layers only, a dense layer 0 and routed layers behind it — against
the benchmark's plain reference (``benchmark/references/exaone_moe.py``,
float32 ``highest``, no kernel or cache).

Sizes: hidden 64, 4 q / 2 kv heads of 32 (heads wider than hidden /
heads), window 8, page 4, 8 experts top-2 + 1 shared, layers L L L G L
with layer 0 dense, vocabulary 64. One engine serves every test here (its
one compile is most of this file's time)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.references import exaone_moe as reference
from paddle_tpu.models import ExaoneMoeConfig, ExaoneMoeForCausalLM
from paddle_tpu.profiler import spans
from paddle_tpu.serving import LLMEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, PAGE, CHUNK = 8, 4, 16
SIZES = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=5,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32,
             sliding_window=W, num_experts=8, num_experts_per_tok=2,
             initializer_range=0.08, dtype="float32")
ENGINE = dict(max_len=128, page_size=PAGE, max_num_seqs=4, chunk_size=CHUNK,
              q_block=4, prefix_caching=False)


def _file_cfg(config):
    """The keys the reference reads from a configuration file."""
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_parameters", "sliding_window", "layer_types",
            "mlp_layer_types", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "expert_offset")
    return {k: getattr(config, k) for k in keys}


@pytest.fixture(scope="module")
def served():
    paddle.seed(11)
    config = ExaoneMoeConfig(**SIZES)
    model = ExaoneMoeForCausalLM(config).eval()
    return model, config, LLMEngine(model, **ENGINE)


def test_layer_kinds_are_static_data_of_the_config():
    kinds = ExaoneMoeConfig(**SIZES).layer_kinds()
    assert [k.window for k in kinds] == [W, W, W, None, W]
    assert [k.rope for k in kinds] == [True, True, True, False, True]
    assert [k.mlp for k in kinds] == ["dense"] + ["sparse"] * 4
    assert all(k.qk_norm for k in kinds)


def test_engine_tokens_sit_on_the_reference_logits(served):
    """Prefill in chunks longer than the window (16 of a 37-token
    prompt) and shorter (its last 5; a whole 5-token prompt), then
    decode through the cache, contexts up to 6 x the window: every token
    the engine emits is the reference's best at its position.

    Tolerance 1e-4 on the distance below the reference's best logit:
    both sides are float32 at ``highest`` (conftest), so they differ by
    accumulation order alone, 1e-6 of logits of size 1; a wrong window,
    page, rotary or expert moves the logit by 1e-2 and more (the planted
    faults below)."""
    model, config, engine = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n).tolist() for n in (37, 5, 21)]
    rids = [engine.add_request(p, max_new_tokens=12) for p in prompts]
    outs = engine.run(max_steps=200)
    assert engine.decode_cache_size() == 1           # one executable
    w, cfg = reference.weights(model), _file_cfg(config)
    worst = 0.0
    for p, rid in zip(prompts, rids):
        got = outs[rid].token_ids
        assert len(got) == 12
        rows = [len(p) - 1 + j for j in range(len(got))]
        lg = reference.logits_at(w, cfg, p + got, rows)
        worst = max(worst, max(reference.margins(lg, got)))
    assert worst <= 1e-4, worst
    # the comparison has power: the same tokens under a reference with
    # the window off by one page, and with one expert's output dropped
    p, got = prompts[0], outs[rids[0]].token_ids
    rows = [len(p) - 1 + j for j in range(len(got))]
    off = dict(cfg, sliding_window=W + PAGE)
    assert max(reference.margins(
        reference.logits_at(w, off, p + got, rows), got)) > 1e-3
    lost = dict(w, layers=[dict(lw) for lw in w["layers"]])
    lost["layers"][2]["e_down"] = lost["layers"][2]["e_down"].at[3].set(0.0)
    assert max(reference.margins(
        reference.logits_at(lost, cfg, p + got, rows), got)) > 1e-3
    # the step log carries the routed layers' and the window's counts
    steps = spans.records("serve.step")[-5:]
    for key in ("moe_pairs_held", "moe_experts_touched",
                "moe_max_expert_tokens", "attn_kv_tokens_live",
                "attn_kv_tokens_read", "window_pages_used", "window_pages"):
        assert all(key in r.attrs for r in steps), key
    last = steps[-1].attrs                    # decode rows only by now
    assert last["moe_pairs_held"] == 4 * 2 * last["rows"]     # nothing away
    assert last["attn_kv_tokens_live"] < 5 * last["live_kv_tokens"]
    # both page groups came back whole
    pool = engine.pool
    pool.check_invariants()
    for rid in rids:
        engine.release(rid)
    assert pool.free_pages == pool.capacity
    assert pool.window_pages_used == 0


def test_window_pages_stay_bounded_while_full_pages_grow(served):
    """A row whose context reaches 12 x the window: its window-group
    pages never exceed window + chunk, its full-group pages cover every
    token; preemption with recompute and a cancel leave both groups
    whole."""
    _, _, engine = served
    pool = engine.pool
    rng = np.random.default_rng(7)
    a = engine.add_request(rng.integers(0, 64, 60).tolist(),
                           max_new_tokens=36)
    b = engine.add_request(rng.integers(0, 64, 30).tolist(),
                           max_new_tokens=36)
    bound = pool.window_row_bound(CHUNK)
    seen, preempted = 0, False
    while engine.has_unfinished():
        engine.step()
        pool.check_invariants()
        for rid in (a, b):
            if rid in pool:
                held = sum(p != 0 for p in pool.window_block_table(rid))
                assert held <= bound
                seen = max(seen, held)
        if not preempted and len(engine.outputs()[a].token_ids) == 10:
            engine.scheduler.preempt(engine._seqs[a])   # recompute path
            preempted = True
        if len(engine.outputs()[b].token_ids) == 20:
            engine.cancel(b)
    assert 0 < seen <= bound
    assert engine.outputs()[a].status == "finished"
    assert len(engine.outputs()[a].token_ids) == 36
    assert engine.outputs()[a].num_preemptions == 1
    assert engine.outputs()[b].status == "cancelled"
    assert pool.free_pages == pool.capacity and pool.window_pages_used == 0
    assert engine.decode_cache_size() == 1


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
    (dict(prefix_caching=True), "prefix_caching=True"),
])
def test_engine_refuses_what_the_layers_do_not_carry(served, mode, match):
    model, _, _ = served
    with pytest.raises(ValueError, match=match):
        LLMEngine(model, **dict(ENGINE, **mode))


def test_handoff_is_refused_by_name(served):
    _, _, engine = served
    with pytest.raises(ValueError, match="extract_request"):
        engine.extract_request("x")
    with pytest.raises(ValueError, match="inject_request"):
        engine.inject_request({})


# ---------------------------------------------------------------------------
# the routed layer alone: shares add up, nothing drops
# ---------------------------------------------------------------------------

def _sparse_layer(seed, held=8, first=0, router_width=8):
    paddle.seed(seed)
    config = ExaoneMoeConfig(**dict(
        SIZES, num_hidden_layers=2, num_experts=held, expert_offset=first,
        router_width=router_width))
    model = ExaoneMoeForCausalLM(config)
    return config, model.serving_params()["layers"][1], \
        reference.weights(model)["layers"][1]


def _routed(lyr, x, cfg):
    from paddle_tpu.serving.spec_decode import _routed_mlp
    stats = []
    live = jnp.ones((x.shape[0],), bool)
    y = _routed_mlp(lyr, x[None], live, cfg, True, stats)[0]
    return np.asarray(y), np.asarray(stats[0])


def _rows(n, seed):
    """Rows as a layer's RMSNorm leaves them (mean square 1)."""
    x = jnp.asarray(np.random.default_rng(seed).standard_normal((n, 64)),
                    jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True))


def _reference_ffn(lw, cfg, x):
    """The reference's sparse feed-forward on already-normed rows."""
    w = dict(lw, ln2=jnp.ones((x.shape[1],), jnp.float32))
    return np.asarray(reference._feed_forward(
        w, dict(_file_cfg(cfg), rms_norm_eps=0.0), x) - x)


def test_the_shares_add_up_to_the_whole_layer():
    """Over shard 0..S-1 of the experts, the routed parts plus the shared
    expert counted once equal the uncut reference layer."""
    cfg, whole, ref_w = _sparse_layer(21)
    x = _rows(24, 2)
    want = _reference_ffn(ref_w, cfg, x)
    shared = np.asarray(reference._swiglu(
        ref_w["gate"], ref_w["up"], ref_w["down"], x, jnp.ones((24,))))
    total, pairs = shared.copy(), 0
    shards = 4
    for s in range(shards):
        lo, n = s * 8 // shards, 8 // shards
        part = dict(whole, **{k: whole[k][lo:lo + n] for k in
                              ("experts_gate", "experts_up", "experts_down")})
        part_cfg = ExaoneMoeConfig(**dict(
            SIZES, num_experts=n, expert_offset=lo, router_width=8))
        y, stats = _routed(part, x, part_cfg)
        total += y - shared                       # its routed part alone
        pairs += int(stats[0])
    assert pairs == 24 * 2                        # every pair landed once
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    # and the uncut layer in one piece
    y, stats = _routed(whole, x, cfg)
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert int(stats[0]) == 48


def test_a_router_that_sends_every_token_to_one_expert_drops_nothing():
    """A GShard gate with capacity 1.25 would give expert 5 at most
    1.25 x 40 x 2 / 8 = 12 slots and drop the other 28 tokens."""
    cfg, lyr, ref_w = _sparse_layer(23)
    bias = jnp.zeros((8,), jnp.float32).at[5].set(10.0)   # steers the choice
    lyr = dict(lyr, router_bias=bias)
    ref_w = dict(ref_w, bias=bias)
    x = _rows(40, 3)
    y, stats = _routed(lyr, x, cfg)
    assert int(stats[2]) == 40                    # all 40 on one expert
    assert int(stats[0]) == 80
    np.testing.assert_allclose(y, _reference_ffn(ref_w, cfg, x),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# check_published
# ---------------------------------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


def test_the_benchmarks_file_passes_and_builds_the_share():
    cfg = _published()
    ExaoneMoeConfig.check_published(cfg)
    import dataclasses
    fields = {f.name for f in dataclasses.fields(ExaoneMoeConfig)}
    config = ExaoneMoeConfig(**{k: v for k, v in cfg.items() if k in fields})
    assert (config.router_width, config.num_experts,
            config.num_experts_per_tok) == (128, 16, 8)
    assert (config.hidden_size, config.num_attention_heads,
            config.num_key_value_heads, config.head_dim) == (6144, 64, 8, 128)
    assert (config.intermediate_size, config.moe_intermediate_size) \
        == (18432, 2048)
    kinds = config.layer_kinds()
    assert len(kinds) == cfg["num_hidden_layers"]
    assert [k.window for k in kinds[:4]] == [128, 128, 128, None]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                   "vocab_size", "num_nextn_predict_layers"}


@pytest.mark.parametrize("change,match", [
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(mlp_bias=True), "mlp_bias"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(n_group=8), "n_group"),
    (dict(topk_group=4), "topk_group"),
    (dict(sliding_window_pattern="LG"), "sliding_window_pattern"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(num_shared_experts=2), "num_shared_experts"),
    (dict(first_k_dense_replace=3), "first_k_dense_replace"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_type"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
])
def test_check_published_refuses_by_name(change, match):
    cfg = dict(_published(), **change)
    with pytest.raises(ValueError, match=match):
        ExaoneMoeConfig.check_published(cfg)
