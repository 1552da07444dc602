"""dots.vlm1.inst's language-model layer (DeepSeek-V3's: latent attention,
YaRN rotary, a group-limited sigmoid router) through the serving engine
at a small size, against the benchmark's plain reference
(``benchmark/references/deepseek_mla.py``: float32 ``highest``, the
published NON-absorbed form, no kernel or cache).

Sizes: hidden 64, 4 heads, ``q_lora_rank`` 24, ``kv_lora_rank`` 32, nope
16, rope 8, v 16 (a cached row of 40 values, held in 128 lanes), YaRN
factor 4 over an original length of 32 so that positions run past it, 16
experts in 4 groups keep 2, top-4 + 1 shared, 1 dense + 3 sparse layers,
vocabulary 64, page 4. One engine serves every engine test here (its one
compile is most of this file's time)."""
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.references import deepseek_mla as reference
from paddle_tpu.kernels.paged_attention import (
    ragged_latent_attention, ragged_latent_attention_reference)
from paddle_tpu.models.deepseek_mla import (DeepseekMlaConfig,
                                            DeepseekMlaForCausalLM,
                                            yarn_inv_freq, yarn_mscale)
from paddle_tpu.nn.moe_dropless import route_sigmoid
from paddle_tpu.profiler import spans
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.kv_cache import PagedKVPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 4, 16
YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
SIZES = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=4,
             num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             max_position_embeddings=512, rope_scaling=YARN,
             first_k_dense_replace=1, n_routed_experts=16,
             num_experts_per_tok=4, n_group=4, topk_group=2,
             initializer_range=0.08, dtype="float32")
ENGINE = dict(max_len=256, page_size=PAGE, max_num_seqs=4, chunk_size=CHUNK,
              q_block=4, prefix_caching=True, num_pages=160)


def _file_cfg(config):
    """A configuration file's keys, as the reference reads them."""
    return dataclasses.asdict(config)


@pytest.fixture(scope="module")
def served():
    paddle.seed(11)
    config = DeepseekMlaConfig(**SIZES)
    model = DeepseekMlaForCausalLM(config).eval()
    return model, config, LLMEngine(model, **ENGINE)


def _margins(model, config, prompt, got, **patch):
    w, cfg = reference.weights(model), dict(_file_cfg(config), **patch)
    rows = [len(prompt) - 1 + j for j in range(len(got))]
    return reference.margins(
        reference.logits_at(w, cfg, prompt + got, rows), got)


def test_layer_kinds_are_static_data_of_the_config():
    config = DeepseekMlaConfig(**SIZES)
    kinds = config.layer_kinds()
    assert all(k.latent for k in kinds)
    assert [k.mlp for k in kinds] == ["dense"] + ["sparse"] * 3
    assert (config.latent_width, config.latent_row) == (40, 128)
    full = DeepseekMlaConfig()
    assert (full.latent_width, full.latent_row) == (576, 640)


def test_engine_tokens_sit_on_the_reference_logits(served, monkeypatch):
    """Prefill in chunks of 16 (a 70-token prompt: four whole chunks and
    6), then decode through the latent cache, on positions under and
    past YaRN's original length of 32 (contexts reach 94): every token
    the engine emits is the reference's best at its position.

    Tolerance 1e-4 on the distance below the reference's best logit:
    both sides are float32 at ``highest`` (conftest), so the absorbed and
    the expanded form differ by accumulation order alone, 1e-6 of logits
    of size 1; a wrong rotary, scale, page or expert moves the logit by
    1e-2 and more (the planted faults below)."""
    model, config, engine = served
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 64, n).tolist() for n in (70, 5, 37)]
    rids = [engine.add_request(p, max_new_tokens=24) for p in prompts]
    outs = engine.run(max_steps=300)
    assert engine.decode_cache_size() == 1           # one executable
    got = [list(outs[r].token_ids) for r in rids]
    assert [len(g) for g in got] == [24] * 3
    worst = max(max(_margins(model, config, p, g))
                for p, g in zip(prompts, got))
    assert worst <= 1e-4, worst
    # the comparison has power. Plain rotary in YaRN's place (the scale
    # keeps its m^2), and the scale without m^2 (YaRN's frequencies kept):
    plain = reference.yarn_inv_freq(dict(_file_cfg(config),
                                         rope_scaling=None))
    with monkeypatch.context() as m:
        m.setattr(reference, "yarn_inv_freq", lambda cfg: plain)
        assert max(_margins(model, config, prompts[0], got[0])) > 1e-3
    with monkeypatch.context() as m:
        m.setattr(reference, "softmax_scale", lambda cfg: 24 ** -0.5)
        assert max(_margins(model, config, prompts[0], got[0])) > 1e-3
    # ... the group limit off, and one expert's output dropped
    assert max(_margins(model, config, prompts[0], got[0],
                        n_group=1, topk_group=1)) > 1e-3
    w = reference.weights(model)
    lost = dict(w, layers=[dict(lw) for lw in w["layers"]])
    lost["layers"][2]["e_down"] = lost["layers"][2]["e_down"].at[3].set(0.0)
    rows = [len(prompts[0]) - 1 + j for j in range(24)]
    assert max(reference.margins(reference.logits_at(
        lost, _file_cfg(config), prompts[0] + got[0], rows), got[0])) > 1e-3
    # the step log carries the latent kernel's and the routed layers'
    # counts
    steps = spans.records("serve.step")[-5:]
    for key in ("attn_qk_pairs", "latent_bytes_held", "moe_pairs_held",
                "moe_experts_touched", "moe_max_expert_tokens"):
        assert all(key in r.attrs for r in steps), key
    last = steps[-1].attrs                    # decode rows only by now
    # a decode token sees its whole context, in each of the 4 layers
    assert last["attn_qk_pairs"] == 4 * last["live_kv_tokens"]
    # one row of 128 float32 a token a layer, by whole pages of 4: the
    # three rows aboard hold at most a page each beyond their tokens
    row = 4 * 128 * 4
    assert last["live_kv_tokens"] * row <= last["latent_bytes_held"] \
        < (last["live_kv_tokens"] + 3 * PAGE) * row
    pool = engine.pool
    pool.check_invariants()
    for rid in rids:
        engine.release(rid)
    assert pool.free_pages == pool.capacity


def test_the_pool_holds_one_latent_array_a_layer(served):
    _, config, engine = served
    pool = engine.pool
    assert pool.latent_row == config.latent_row == 128
    assert len(pool.kv) == 4
    for pages in pool.kv:                       # no K/V pair, no kv heads
        assert isinstance(pages, jax.Array)
        assert pages.shape == (ENGINE["num_pages"], PAGE, 128)
    assert pool.kv_bytes_per_token == 4 * 128 * 4
    assert pool.pool_bytes == ENGINE["num_pages"] * PAGE * 4 * 128 * 4
    with pytest.raises(ValueError, match="latent rows"):
        pool.export_pages("nobody")
    with pytest.raises(ValueError, match="latent rows"):
        pool.adopt_sequence("x", 4, [{}] * 4)


def test_preemption_cancel_and_a_forked_prefix_leave_the_pool_whole(served):
    """Preemption with recompute and a cancel; then two requests that
    share a 40-token prefix: the second forks the first's pages (reads
    the same latent rows), and both emit what the reference says."""
    model, config, engine = served
    pool = engine.pool
    rng = np.random.default_rng(7)
    a = engine.add_request(rng.integers(0, 64, 60).tolist(),
                           max_new_tokens=30)
    b = engine.add_request(rng.integers(0, 64, 30).tolist(),
                           max_new_tokens=30)
    preempted = False
    while engine.has_unfinished():
        engine.step()
        pool.check_invariants()
        if not preempted and len(engine.outputs()[a].token_ids) == 10:
            engine.scheduler.preempt(engine._seqs[a])   # recompute path
            preempted = True
        if len(engine.outputs()[b].token_ids) == 20:
            engine.cancel(b)
    assert engine.outputs()[a].status == "finished"
    assert len(engine.outputs()[a].token_ids) == 30
    assert engine.outputs()[a].num_preemptions == 1
    assert engine.outputs()[b].status == "cancelled"
    for rid in (a, b):
        engine.release(rid)
    assert pool.free_pages == pool.capacity
    # a shared prefix: the donor finishes its prompt first
    prefix = rng.integers(0, 64, 40).tolist()
    p1 = prefix + rng.integers(0, 64, 9).tolist()
    p2 = prefix + rng.integers(0, 64, 13).tolist()
    r1 = engine.add_request(p1, max_new_tokens=8)
    while not engine.outputs()[r1].token_ids:
        engine.step()
    r2 = engine.add_request(p2, max_new_tokens=8)
    engine.step()
    shared = [p for p in pool.block_table(r2) if pool.page_refcount(p) > 1]
    assert len(shared) == 40 // PAGE                 # the prefix's pages
    assert pool.block_table(r1)[:len(shared)] == shared
    outs = engine.run(max_steps=200)
    for p, rid in ((p1, r1), (p2, r2)):
        got = list(outs[rid].token_ids)
        assert max(_margins(model, config, p, got)) <= 1e-4
        engine.release(rid)
    pool.check_invariants()
    assert pool.free_pages == pool.capacity
    assert engine.decode_cache_size() == 1


def test_a_latent_pool_forks_copies_on_write_and_rolls_back():
    pool = PagedKVPool(2, 1, 128, num_pages=12, page_size=4,
                       latent_row=128)
    pool.allocate("a", 10)                            # 3 pages
    rows = jnp.arange(3 * 4 * 128, dtype=jnp.float32).reshape(3, 4, 128)
    idx = jnp.asarray(pool.block_table("a"))
    pool.kv = [C.at[idx].set(rows + li) for li, C in enumerate(pool.kv)]
    pool.fork("b", "a", 10)
    assert pool.block_table("b") == pool.block_table("a")
    assert pool.prepare_append("b", 11) == 1          # the tail page CoWs
    new = pool.block_table("b")[2]
    assert new != pool.block_table("a")[2]
    for li, C in enumerate(pool.kv):                  # the same latent rows
        np.testing.assert_array_equal(C[new], rows[2] + li)
    pool.rollback("b", 9)
    assert pool.seq_len("b") == 9
    pool.check_invariants()
    pool.free("a")
    pool.free("b")
    assert pool.free_pages == pool.capacity
    with pytest.raises(ValueError, match="no kv-head axis"):
        PagedKVPool(2, 8, 128, num_pages=12, page_size=4, latent_row=128)
    with pytest.raises(ValueError, match="latent pool"):
        PagedKVPool(2, 1, 128, num_pages=12, page_size=4, latent_row=128,
                    dtype=jnp.int8)


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
    (dict(prefix_store="/nowhere"), "prefix_store"),
    (dict(pinned_prefix_pages=8), "pinned_prefix_pages"),
])
def test_engine_refuses_what_the_latent_kind_does_not_carry(served, mode,
                                                            match):
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
# one layer's attention: absorbed (latent rows) against expanded (a head)
# ---------------------------------------------------------------------------

def test_absorbed_attention_equals_the_expanded_form(monkeypatch):
    """One layer's attention output computed from the latent rows (the
    program's absorbed step: append, kernel, un-absorb) equals the
    reference's per-head form on the same input, float32 tight; the pool
    afterwards holds exactly ``kv_lora_rank + qk_rope_head_dim`` values a
    token (in the row's 128 lanes: the rest stay zero)."""
    from paddle_tpu.serving.spec_decode import (_latent_attention,
                                                _page_slots,
                                                _ragged_packing)
    paddle.seed(3)
    config = DeepseekMlaConfig(**dict(SIZES, num_hidden_layers=1))
    model = DeepseekMlaForCausalLM(config)
    lyr = model.serving_params()["layers"][0]
    lw = reference.weights(model)["layers"][0]
    s = 128                                     # one row, past the original 32
    h = jnp.asarray(np.random.default_rng(1).standard_normal((s, 64)),
                    jnp.float32)
    pages = s // PAGE
    Cp = jnp.zeros((pages + 1, PAGE, 128), jnp.float32)
    tbls = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    q_starts, q_lens = jnp.array([0], jnp.int32), jnp.array([s], jnp.int32)
    tok_row, live = _ragged_packing(q_starts, q_lens, s)
    positions = jnp.arange(s, dtype=jnp.int32)
    slot = _page_slots(positions, tbls, tok_row, live, PAGE, pages)
    out, Cp = _latent_attention(lyr, h[None], Cp, positions, slot, tbls,
                                q_starts, q_lens, q_lens, config, 4, True)
    cfg = _file_cfg(config)
    want = reference.attention(lw, cfg, jnp.array(h))
    np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-5)
    rows = np.asarray(Cp[1:]).reshape(s, 128)
    assert np.all(rows[:, 40:] == 0) and np.all(np.any(rows[:, :40] != 0, 1))
    assert np.all(np.asarray(Cp[0]) == 0)             # the null page
    # plain rotary, or the scale without m^2, is another function
    plain = reference.yarn_inv_freq(dict(cfg, rope_scaling=None))
    for name, fake in (("yarn_inv_freq", lambda cfg: plain),
                       ("softmax_scale", lambda cfg: 24 ** -0.5)):
        with monkeypatch.context() as m:
            m.setattr(reference, name, fake)
            off = reference.attention(lw, cfg, jnp.array(h))
        assert float(jnp.abs(off - want).max()) > 1e-3, name


# ---------------------------------------------------------------------------
# the kernel against its jnp reference
# ---------------------------------------------------------------------------

KERNEL_CASES = {
    # (q_len, kv_len) a row; pages of 16, so a slab is 256 tokens
    "decode_and_prefill_rows_in_one_launch":
        [(1, 13), (20, 57), (1, 290), (7, 7)],
    "contexts_that_end_mid_page_and_mid_slab": [(1, 257), (5, 300), (1, 3)],
    "a_chunk_longer_than_a_slab": [(300, 300)],
    "one_row_alone": [(1, 41)],
    "a_chunk_behind_a_long_prefix": [(24, 600)],
}


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_ragged_latent_attention_matches_its_reference(name):
    rows = KERNEL_CASES[name]
    heads, width, v_width, qb, pps, page = 4, 40, 32, 8, 40, 16
    rng = np.random.default_rng(len(name))
    q_lens = np.array([r[0] for r in rows] + [0], np.int32)   # a pad row
    kv_lens = np.array([r[1] for r in rows] + [0], np.int32)
    slots = -(-q_lens // qb) * qb
    t = int(slots.sum()) + qb                    # a dead block at the end
    q_starts = np.where(q_lens > 0, np.cumsum(slots) - slots, t) \
        .astype(np.int32)
    n_pages = 1 + int((-(-kv_lens // page)).sum())
    perm = rng.permutation(np.arange(1, n_pages))
    tbl, at = np.zeros((len(q_lens), pps), np.int32), 0
    for i, kl in enumerate(kv_lens):
        n = -(-int(kl) // page)
        tbl[i, :n] = perm[at:at + n]
        at += n
    c = jnp.asarray(rng.standard_normal((n_pages, page, width)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((t, heads, width)), jnp.float32)
    got = ragged_latent_attention(
        q, c, jnp.asarray(tbl), jnp.asarray(q_starts), jnp.asarray(q_lens),
        jnp.asarray(kv_lens), v_width=v_width, scale=0.3, q_block=qb,
        interpret=True)
    want = ragged_latent_attention_reference(
        q, c, tbl, q_starts, q_lens, kv_lens, v_width=v_width, scale=0.3)
    assert bool(jnp.isfinite(got).all())         # padding rows included
    for s, n in zip(q_starts, q_lens):
        np.testing.assert_allclose(got[s:s + n], want[s:s + n], rtol=1e-5,
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# the routed layer: shares add up, the group limit
# ---------------------------------------------------------------------------

def _sparse_layer(seed, held=16, first=0):
    paddle.seed(seed)
    config = DeepseekMlaConfig(**dict(
        SIZES, num_hidden_layers=2, n_routed_experts=held,
        expert_offset=first, router_width=16))
    model = DeepseekMlaForCausalLM(config)
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
    return np.asarray(reference.feed_forward(
        w, dict(_file_cfg(cfg), rms_norm_eps=0.0), jnp.array(x)) - x)


def test_the_shares_add_up_to_the_whole_layer():
    """Over shards 0..3 of the experts (a shard is one routing group),
    the routed parts plus the shared expert counted once equal the uncut
    reference layer, with the group limit on: a token whose two kept
    groups lie on other shards adds nothing here, and the router still
    counted it."""
    cfg, whole, ref_w = _sparse_layer(21)
    x = _rows(128, 2)
    want = _reference_ffn(ref_w, cfg, x)
    with jax.default_matmul_precision("highest"):
        shared = np.asarray(reference._swiglu(
            ref_w["gate"], ref_w["up"], ref_w["down"], x))
    total, pairs, idle = shared.copy(), 0, 0
    for s in range(4):
        lo, n = 4 * s, 4
        part = dict(whole, **{k: whole[k][lo:lo + n] for k in
                              ("experts_gate", "experts_up", "experts_down")})
        part_cfg = DeepseekMlaConfig(**dict(
            SIZES, num_hidden_layers=2, n_routed_experts=n,
            expert_offset=lo, router_width=16))
        y, stats = _routed(part, x, part_cfg)
        total += y - shared                       # its routed part alone
        pairs += int(stats[0])
        idle += int(np.sum(np.all(np.abs(y - shared) < 1e-7, -1)))
    assert pairs == 128 * 4                       # every pair landed once
    # 2 of 4 groups kept: a token is absent from two shards
    assert idle == 128 * 2
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    y, stats = _routed(whole, x, cfg)             # the uncut layer
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)
    assert int(stats[0]) == 128 * 4


def _scores_router(scores):
    """``x``, ``router`` and ``bias`` under which ``route_sigmoid`` scores
    a token's experts exactly ``sigmoid(scores)``."""
    scores = jnp.asarray(scores, jnp.float32)
    e = scores.shape[1]
    return scores, jnp.eye(e, dtype=jnp.float32), jnp.zeros((e,), jnp.float32)


def test_the_group_limit_drops_the_experts_of_groups_not_kept():
    """4 groups of 4, keep 2, top-4. The token's four best experts lie in
    groups 0, 1, 2 and 3 (one each); group scores (sum of the two best)
    keep groups 0 and 1, so the best experts of groups 2 and 3 are lost
    and the next best of groups 0 and 1 take their place."""
    s = np.full((1, 16), -4.0, np.float32)
    s[0, [0, 4, 8, 12]] = [3.0, 2.9, 2.8, 2.7]      # the four best overall
    s[0, [1, 5]] = [2.0, 1.9]                       # seconds of groups 0, 1
    s[0, [9, 13]] = [0.5, 0.4]                      # of groups 2, 3: lower
    x, router, bias = _scores_router(s)
    kw = dict(top_k=4, scaling=2.5)
    free, _ = route_sigmoid(x, router, bias, **kw)
    assert sorted(np.asarray(free[0])) == [0, 4, 8, 12]
    idx, gates = route_sigmoid(x, router, bias, n_group=4, topk_group=2,
                               **kw)
    assert sorted(np.asarray(idx[0])) == [0, 1, 4, 5]
    sig = 1 / (1 + np.exp(-s[0, np.asarray(idx[0])]))
    np.testing.assert_allclose(gates[0], 2.5 * sig / sig.sum(), rtol=1e-6)
    # the bias steers the choice of GROUPS too, and never the gate
    biased = bias.at[8].set(5.0).at[9].set(5.0)
    idx, gates = route_sigmoid(x, router, biased, n_group=4, topk_group=2,
                               **kw)
    assert sorted(np.asarray(idx[0])) == [0, 1, 8, 9]
    sig = 1 / (1 + np.exp(-s[0, np.asarray(idx[0])]))
    np.testing.assert_allclose(gates[0], 2.5 * sig / sig.sum(), rtol=1e-6)


def test_the_group_limit_at_the_published_widths():
    """256 experts in 8 groups of 32, keep 4, top-8 (router alone): every
    chosen expert lies in one of the 4 groups with the largest sum of
    their two best scores, and is among the 8 best of those groups; the
    reference's router chooses the same."""
    rng = np.random.default_rng(4)
    s = rng.standard_normal((64, 256)).astype(np.float32)
    x, router, bias = _scores_router(s)
    idx, gates = route_sigmoid(x, router, bias, top_k=8, scaling=2.5,
                               n_group=8, topk_group=4)
    sig = 1 / (1 + np.exp(-s.astype(np.float64)))    # the choice scores
    per = np.sort(sig.reshape(64, 8, 32), -1)[..., -2:].sum(-1)
    for t in range(64):
        kept = set(np.argsort(-per[t])[:4])
        chosen = np.asarray(idx[t])
        assert {int(e) // 32 for e in chosen} <= kept
        allowed = [e for e in range(256) if e // 32 in kept]
        best = sorted(allowed, key=lambda e: -s[t, e])[:8]
        assert sorted(chosen) == sorted(best)
    with jax.default_matmul_precision("highest"):
        dense = reference._gates(router, bias, x, top_k=8, scaling=2.5,
                                 norm=True, n_group=8, topk_group=4)
    got = np.zeros((64, 256), np.float32)
    np.put_along_axis(got, np.asarray(idx), np.asarray(gates), 1)
    np.testing.assert_allclose(got, dense, rtol=1e-6, atol=1e-7)


def _route_sigmoid_before(x, router, bias, *, top_k, scaling,
                          norm_topk_prob=True):
    """``route_sigmoid`` as it stood before the group limit (PR 29)."""
    F32 = jnp.float32
    s = jax.nn.sigmoid(jnp.dot(x.astype(F32), router.astype(F32),
                               precision="highest"))
    _, idx = jax.lax.top_k(s + bias.astype(F32), top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk_prob:
        g = g / jnp.sum(g, -1, keepdims=True)
    return idx.astype(jnp.int32), g * scaling


def test_one_group_lowers_what_route_sigmoid_lowered_before():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((32, 64)), jnp.bfloat16)
    router = jnp.asarray(rng.standard_normal((64, 128)) * 0.02, jnp.bfloat16)
    bias = jnp.asarray(rng.standard_normal((128,)) * 0.01, jnp.float32)
    kw = dict(top_k=8, scaling=2.5)

    def text(fn, **more):
        t = jax.jit(lambda *a: fn(*a, **kw, **more)).lower(
            x, router, bias).as_text()
        return t.replace(fn.__name__, "f")
    assert text(route_sigmoid, n_group=1, topk_group=1) \
        == text(_route_sigmoid_before)
    a = route_sigmoid(x, router, bias, **kw)
    b = _route_sigmoid_before(x, router, bias, **kw)
    assert all(bool(jnp.array_equal(p, q)) for p, q in zip(a, b))


# ---------------------------------------------------------------------------
# YaRN at the published numbers
# ---------------------------------------------------------------------------

def test_yarn_at_the_published_numbers():
    """factor 40, original length 4096, beta 32 / 1, theta 10000, 64
    rotary dims. d(32) = 64 ln(4096 / 64 pi) / (2 ln 1e4) = 10.47, d(1) =
    22.52: pairs 0-10 keep their frequency, pairs 23-31 are divided by
    40, a ramp of thirteenths between."""
    inv = yarn_inv_freq(64, 10000.0, 40, 4096, 32, 1)
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert inv.dtype == np.float32 and inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], f[23:] / 40, rtol=1e-6)
    for i in (11, 16, 22):
        ramp = (i - 10) / 13
        np.testing.assert_allclose(inv[i], f[i] * (1 - ramp + ramp / 40),
                                   rtol=1e-6)
    np.testing.assert_allclose(inv[16], 0.01 * (7 / 13 + 6 / 13 / 40),
                               rtol=1e-6)               # f_16 = 1e4^-0.5
    m = yarn_mscale(40, 1)
    assert abs(m - 1.3688879) < 1e-6                    # 0.1 ln 40 + 1
    config = DeepseekMlaConfig()
    np.testing.assert_array_equal(config.rope_inv_freq(), inv)
    assert abs(config.softmax_scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(config.softmax_scale - 0.135234) < 1e-6   # 0.0721688 x 1.873854
    # the reference wrote its own
    published = {"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                 "rope_theta": 10000, "rope_scaling": config.rope_scaling}
    np.testing.assert_allclose(reference.yarn_inv_freq(published), inv,
                               rtol=1e-7)
    assert abs(reference.softmax_scale(published)
               - config.softmax_scale) < 1e-12
    # without scaling: plain rotary, plain scale
    plain = DeepseekMlaConfig(rope_scaling=None)
    np.testing.assert_allclose(plain.rope_inv_freq(), f, rtol=1e-6)
    assert abs(plain.softmax_scale - 192 ** -0.5) < 1e-12
    assert math.isclose(yarn_mscale(1.0), 1.0)


# ---------------------------------------------------------------------------
# check_published
# ---------------------------------------------------------------------------

def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "dots-vlm1-inst.json")) as f:
        return json.load(f)


def test_the_benchmarks_file_passes_and_builds_the_share():
    cfg = _published()
    DeepseekMlaConfig.check_published(cfg)
    fields = {f.name for f in dataclasses.fields(DeepseekMlaConfig)}
    config = DeepseekMlaConfig(**{k: v for k, v in cfg.items()
                                  if k in fields})
    assert (config.router_width, config.n_routed_experts, config.n_group,
            config.topk_group, config.num_experts_per_tok) \
        == (256, 16, 8, 4, 8)
    assert (config.hidden_size, config.num_attention_heads,
            config.q_lora_rank, config.kv_lora_rank) == (7168, 128, 1536, 512)
    assert (config.qk_nope_head_dim, config.qk_rope_head_dim,
            config.v_head_dim) == (128, 64, 128)
    assert (config.intermediate_size, config.moe_intermediate_size) \
        == (18432, 2048)
    assert config.mlp_layer_types == cfg["mlp_layer_types"] \
        == ["dense"] + ["sparse"] * 5
    assert config.latent_row == 640
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers", "vision_tower"}
    assert all({"published", "run", "why"} <= set(v)
               for v in cfg["reduced"].values())
    assert "sixteen chips" in cfg["stands_for"]


@pytest.mark.parametrize("change,match", [
    (dict(rope_scaling={"type": "linear", "factor": 4}), "rope_scaling.type"),
    (dict(rope_scaling=dict(YARN, mscale=0.7)), "mscale"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(n_shared_experts=2), "n_shared_experts"),
    (dict(vision_config={"num_hidden_layers": 42}, reduced={}),
     "vision_config"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(q_lora_rank=None), "q_lora_rank"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(tie_word_embeddings=True), "tie_word_embeddings"),
    (dict(num_key_value_heads=8), "num_key_value_heads"),
    (dict(ep_size=8), "ep_size"),
    (dict(mlp_layer_types=["sparse"] * 6), "mlp_layer_types"),
])
def test_check_published_refuses_by_name(change, match):
    cfg = dict(_published(), **change)
    with pytest.raises(ValueError, match=match):
        DeepseekMlaConfig.check_published(cfg)


def test_a_declared_vision_tower_passes():
    cfg = dict(_published(), vision_config={"num_hidden_layers": 42})
    DeepseekMlaConfig.check_published(cfg)     # "vision_tower" is reduced


def test_the_other_config_classes_keep_refusing_rope_scaling():
    from benchmark import build
    from paddle_tpu.models import ExaoneMoeConfig, LlamaConfig
    for cls in (ExaoneMoeConfig, LlamaConfig):
        cfg = {"num_hidden_layers": 2, "num_attention_heads": 4,
               "hidden_size": 64, "rope_scaling": YARN,
               "rope_parameters": {"rope_theta": 1e4,
                                   "rope_type": "default"}}
        with pytest.raises(ValueError, match="rope_scaling"):
            build.published_check(cls)(cfg)
