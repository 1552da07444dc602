"""Ragged prefill megakernel gates (ISSUE 20).

The tentpole contracts (kernels/prefill_megakernel.py,
models/generation.py, serving/engine.py):

- the fused prefill-layer kernel (rms_norm -> qkv -> rope -> ragged
  paged attention -> KV append -> o-proj -> residual -> rms_norm ->
  swiglu -> residual over ONE packed ragged chunk) matches its jnp
  fallback — fp and int8 weights, fp and int8 KV pools, mixed
  prefill/decode/continuation/pad rows, with the NULL page (page 0)
  excluded from the pool contract on both sides;
- ``FLAGS_prefill_megakernel=fused`` is token-IDENTICAL to the unfused
  engine across chunked prefill at a pinned ``step_token_budget``
  (chunk boundaries land mid-prompt), CoW prefix forks, page-pressure
  preemption, spec-decode verification rounds and the two-tier
  spill/prefetch arena — while the ragged trace count stays at ONE;
- the compiled ragged step gets structurally CHEAPER: fused
  fusion/kernel counts land strictly below the unfused lowering's, and
  ``Generator.prefill_lowering`` collapses L layer-body marker sites
  to one;
- ``hlo_forensics.mixed_launch_stats`` decomposes marker counts over
  heterogeneous body kinds and refuses to fabricate when the
  decomposition is ambiguous or impossible (satellite 1);
- the autotune cache key carries ``(q_block, scope, num_layers)`` so
  prefill tunings never collide across geometry (satellite 2);
- ``ServingMetrics.prefill_launches`` counts one launch per step that
  served prefill rows, and ``prefill_chunk`` spans carry the fused
  attribution (satellite 6);
- ``FLAGS_prefill_megakernel`` validates through the flags on_set
  rollback path, and a runtime Pallas failure reroutes through
  ``FLAGS_enable_fusion_fallback`` with the mode reporting ``jnp``.
"""
import functools

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.flags import GLOBAL_FLAGS, set_flags
from paddle_tpu.jit.hlo_forensics import (fusion_stats, launch_stats,
                                          mixed_launch_stats)
from paddle_tpu.kernels.prefill_megakernel import (
    _reference_prefill_layer, fuse_layer_weights, fused_prefill_layer,
    prefill_fallback_tripped, prefill_megakernel_mode, ragged_prologue,
    reset_prefill_fallback)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config, Generator
from paddle_tpu.quantization.low_bit import quantize_weight
from paddle_tpu.serving import LLMEngine, RequestTracer


@pytest.fixture(scope="module")
def deep_model():
    """3 layers: deep enough that the prefill layer loop's structure
    (unrolled vs scanned) is observable, small enough for the CPU
    tier."""
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=3, hidden_size=64,
                            intermediate_size=96, num_attention_heads=4,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _prompts(model, lengths, seed=0):
    rng = np.random.RandomState(seed)
    v = model.config.vocab_size
    return [rng.randint(0, v, (n,)).tolist() for n in lengths]


def _run_engine(model, prompts, max_new=8, **kw):
    eng = LLMEngine(model, max_len=64, page_size=4, max_num_seqs=4, **kw)
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    outs = eng.run(max_steps=400)
    return [outs[r].token_ids for r in rids], eng


def _layer_fixture(seed=0, T=32, R=4, D=64, H=4, Hkv=2, dh=16, F=96,
                   PPS=6, ps=8, P=16, qb=8):
    """One packed ragged chunk with genuinely mixed traffic: a full
    prefill chunk (q_len=8, kv==q), a decode row (q_len=1 continuing
    kv_len=5), a continuation chunk (q_len=13 atop 7 cached tokens) and
    a pad row — over distinct (non-aliased) pages per row."""
    rng = np.random.default_rng(seed)

    def arr(*s):
        return jnp.asarray(rng.standard_normal(s).astype(np.float32) * 0.3)

    layer = {"ln1": arr(D) + 1.0, "ln2": arr(D) + 1.0,
             "q": arr(D, H * dh), "k": arr(D, Hkv * dh),
             "v": arr(D, Hkv * dh), "o": arr(H * dh, D),
             "gate": arr(D, F), "up": arr(D, F), "down": arr(F, D)}
    h = arr(1, T, D)
    Kp, Vp = arr(Hkv, P, ps, dh), arr(Hkv, P, ps, dh)
    tbls = np.full((R, PPS), 0, np.int32)
    tbls[:, :3] = rng.permutation(np.arange(1, P))[:R * 3].reshape(R, 3)
    tbls = jnp.asarray(tbls)
    q_lens = np.array([8, 1, 13, 0], np.int32)
    q_starts = np.array([0, 8, 16, T], np.int32)   # slots: q_block-aligned
    kv_lens = np.array([8, 5, 20, 0], np.int32)
    positions = np.zeros((T,), np.int32)
    for r in range(R):
        for t in range(q_lens[r]):
            positions[q_starts[r] + t] = kv_lens[r] - q_lens[r] + t
    positions = jnp.asarray(positions)
    q_starts, q_lens, kv_lens = map(jnp.asarray, (q_starts, q_lens,
                                                  kv_lens))
    pre = ragged_prologue(positions, tbls, q_starts, q_lens,
                          theta=10000.0, head_dim=dh, page_size=ps,
                          max_pages=PPS, q_block=qb)
    return (layer, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
            dict(eps=1e-6, num_heads=H, q_block=qb))


# ---------------------------------------------------------------------------
# kernel parity: the Pallas body vs the bitwise-fused jnp reference
# ---------------------------------------------------------------------------

def _live_tokens(q_starts, q_lens):
    """Rows of the packed chunk that hold a live token: slot padding
    holds garbage by the ragged kernel's contract, and the two bodies'
    attention need not agree on which."""
    return np.concatenate([np.arange(s, s + n) for s, n in
                           zip(np.asarray(q_starts), np.asarray(q_lens))])


def test_fused_prefill_layer_matches_reference_fp():
    (layer, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
     kw) = _layer_fixture()
    fused = fuse_layer_weights(layer)
    ref = _reference_prefill_layer(
        fused, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
        eps=kw["eps"], num_heads=kw["num_heads"],
        num_kv_heads=Kp.shape[0], head_dim=Kp.shape[3],
        page_size=Kp.shape[2], q_block=kw["q_block"],
        attn_interpret=True)
    out = fused_prefill_layer(fused, h, Kp, Vp, tbls, pre, q_starts,
                              q_lens, kv_lens, interpret=True,
                              attn_interpret=True, **kw)
    live = _live_tokens(q_starts, q_lens)
    np.testing.assert_allclose(np.asarray(out[0])[:, live],
                               np.asarray(ref[0])[:, live],
                               rtol=1e-4, atol=1e-4)
    # page 0 is the NULL/trash page: the jnp scatter dumps dead-token
    # rows there, the kernel preserves committed bytes — both
    # unspecified by the pool contract
    for i in (1, 2):
        np.testing.assert_allclose(np.asarray(out[i][:, 1:]),
                                   np.asarray(ref[i][:, 1:]),
                                   rtol=1e-5, atol=1e-5)


def test_fused_prefill_layer_matches_reference_int8():
    """int8 weights AND int8 KV pools: pools, scales and appended bytes
    are bitwise the reference's (the requant-append runs outside the
    kernel on both paths)."""
    from paddle_tpu.serving.engine import _segmented_quant_append
    (layer, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
     kw) = _layer_fixture()
    qlayer = dict(layer)
    for k in ("q", "k", "v", "o", "gate", "up", "down"):
        qlayer[k] = quantize_weight(layer[k], "weight_only_int8")
    qfused = fuse_layer_weights(qlayer)
    assert qfused is not None

    rng = np.random.default_rng(11)
    Hkv, P, ps, dh = Kp.shape
    PPS = tbls.shape[1]
    Kq = jnp.asarray(rng.integers(-127, 128, Kp.shape),
                     jnp.int8).astype(jnp.float32)
    Vq = jnp.asarray(rng.integers(-127, 128, Vp.shape), jnp.float32)
    Ks0 = jnp.asarray(rng.uniform(0.01, 0.05, (Hkv, P)), jnp.float32)
    Vs0 = jnp.asarray(rng.uniform(0.01, 0.05, (Hkv, P)), jnp.float32)

    def qafn(Kp_, Ks_, Vp_, Vs_, kt, vt):
        Kp_, Ks_ = _segmented_quant_append(Kp_, Ks_, kt, tbls, q_starts,
                                           q_lens, kv_lens, ps, PPS, P)
        Vp_, Vs_ = _segmented_quant_append(Vp_, Vs_, vt, tbls, q_starts,
                                           q_lens, kv_lens, ps, PPS, P)
        return Kp_, Ks_, Vp_, Vs_

    ref = _reference_prefill_layer(
        qfused, h, Kq, Vq, tbls, pre, q_starts, q_lens, kv_lens,
        eps=kw["eps"], num_heads=kw["num_heads"], num_kv_heads=Hkv,
        head_dim=dh, page_size=ps, q_block=kw["q_block"],
        attn_interpret=True, k_scales=Ks0, v_scales=Vs0,
        quant_append_fn=qafn)
    out = fused_prefill_layer(qfused, h, Kq, Vq, tbls, pre, q_starts,
                              q_lens, kv_lens, interpret=True,
                              attn_interpret=True, k_scales=Ks0,
                              v_scales=Vs0, quant_append_fn=qafn, **kw)
    live = _live_tokens(q_starts, q_lens)
    np.testing.assert_allclose(np.asarray(out[0])[:, live],
                               np.asarray(ref[0])[:, live],
                               rtol=1e-4, atol=1e-4)
    for i in (1, 2, 3, 4):
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(ref[i]))


def test_fuse_layer_weights_column_exact_and_refusals():
    layer = _layer_fixture()[0]
    fused = fuse_layer_weights(layer)
    H_dh = layer["q"].shape[1]
    Hkv_dh = layer["k"].shape[1]
    np.testing.assert_array_equal(np.asarray(fused["qkv"][:, :H_dh]),
                                  np.asarray(layer["q"]))
    np.testing.assert_array_equal(
        np.asarray(fused["qkv"][:, H_dh:H_dh + Hkv_dh]),
        np.asarray(layer["k"]))
    np.testing.assert_array_equal(
        np.asarray(fused["qkv"][:, H_dh + Hkv_dh:]),
        np.asarray(layer["v"]))
    F = layer["gate"].shape[1]
    np.testing.assert_array_equal(np.asarray(fused["gateup"][:, :F]),
                                  np.asarray(layer["gate"]))
    # int8 concatenates exactly too (per-output-column scales)
    qlayer = {k: (quantize_weight(v, "weight_only_int8")
                  if k not in ("ln1", "ln2") else v)
              for k, v in layer.items()}
    qfused = fuse_layer_weights(qlayer)
    np.testing.assert_array_equal(
        np.asarray(qfused["qkv"].qdata[:, :H_dh]),
        np.asarray(qlayer["q"].qdata))
    np.testing.assert_array_equal(
        np.asarray(qfused["qkv"].scale[:H_dh]),
        np.asarray(qlayer["q"].scale).reshape(-1))
    # int4 (packed nibbles) and mixed layouts have no column-exact
    # concat: the caller must keep the unfused bodies
    i4layer = {k: (quantize_weight(v, "weight_only_int4")
                   if k not in ("ln1", "ln2") else v)
               for k, v in layer.items()}
    assert fuse_layer_weights(i4layer) is None
    mixed = dict(qlayer, o=layer["o"])
    assert fuse_layer_weights(mixed) is None
    assert prefill_megakernel_mode(None) == "jnp"


def test_rank_right_matches_searchsorted():
    """The broadcast compare-sum that replaced searchsorted (the
    sequential while-kernel in the lowering) is value-identical."""
    from paddle_tpu.kernels.prefill_megakernel import _rank_right
    q_starts = np.array([0, 8, 9, 9, 32], np.int32)
    v = np.arange(-2, 40, dtype=np.int32)
    want = np.maximum(
        np.searchsorted(q_starts, v, side="right") - 1, 0)
    got = _rank_right(jnp.asarray(q_starts), jnp.asarray(v))
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# flag + fallback honesty
# ---------------------------------------------------------------------------

def test_prefill_flag_validates_via_on_set_rollback():
    old = GLOBAL_FLAGS.get("prefill_megakernel")
    try:
        with pytest.raises(ValueError, match="prefill_megakernel"):
            set_flags({"prefill_megakernel": "kernel"})
        assert GLOBAL_FLAGS.get("prefill_megakernel") == old
        set_flags({"prefill_megakernel": "fused"})
        assert GLOBAL_FLAGS.get("prefill_megakernel") == "fused"
    finally:
        GLOBAL_FLAGS.set("prefill_megakernel", old)


def test_prefill_flag_feeds_engine_and_generator_defaults(deep_model):
    old = GLOBAL_FLAGS.get("prefill_megakernel")
    prompt = _prompts(deep_model, [5], seed=25)[0]
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    try:
        set_flags({"prefill_megakernel": "fused"})
        eng = LLMEngine(deep_model, max_len=32, page_size=4)
        assert eng.prefill_megakernel == "fused"
        gen = Generator(deep_model, max_len=64)
        assert gen.prefill_megakernel == "fused"
        out = gen.generate(ids, max_new_tokens=8, burst_tokens=1).numpy()
        set_flags({"prefill_megakernel": "unfused"})
        ref = Generator(deep_model, max_len=64).generate(
            ids, max_new_tokens=8, burst_tokens=1).numpy()
        assert (out == ref).all()
    finally:
        GLOBAL_FLAGS.set("prefill_megakernel", old)


def test_prefill_mode_reports_jnp_after_tripped_fallback(monkeypatch):
    """When FLAGS_enable_fusion_fallback rerouted a failed Pallas
    launch to the jnp body at run time, prefill_megakernel_mode must
    say ``jnp`` — not echo the environment's kernel selection — until
    the trip is reset."""
    import paddle_tpu.kernels.prefill_megakernel as pm
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    reset_prefill_fallback()
    (layer, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
     kw) = _layer_fixture()
    fused = fuse_layer_weights(layer)
    assert not prefill_fallback_tripped()
    assert prefill_megakernel_mode(fused) == "interpret"

    ref = _reference_prefill_layer(
        fused, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
        eps=kw["eps"], num_heads=kw["num_heads"],
        num_kv_heads=Kp.shape[0], head_dim=Kp.shape[3],
        page_size=Kp.shape[2], q_block=kw["q_block"],
        attn_interpret=True)

    def boom(*a, **k):
        raise RuntimeError("simulated pallas lowering failure")

    # shim pl ONLY inside prefill_megakernel's namespace: the jnp
    # reference body still runs the real (interpreted) ragged attention
    real_pl = pm.pl

    class _Shim:
        pallas_call = staticmethod(boom)

        def __getattr__(self, name):
            return getattr(real_pl, name)
    monkeypatch.setattr(pm, "pl", _Shim())
    call = functools.partial(
        fused_prefill_layer, fused, h, Kp, Vp, tbls, pre, q_starts,
        q_lens, kv_lens, interpret=True, attn_interpret=True, **kw)
    # the reroute is opt-in: by default a refused kernel fails loudly
    assert GLOBAL_FLAGS.get("enable_fusion_fallback") is False
    with pytest.raises(RuntimeError, match="simulated pallas"):
        call()
    assert not prefill_fallback_tripped()
    GLOBAL_FLAGS.set("enable_fusion_fallback", True)
    try:
        out = call()
        # the fallback still computed the right answer...
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=1e-5, atol=1e-5)
        # ...and the mode now admits the reroute
        assert prefill_fallback_tripped()
        assert prefill_megakernel_mode(fused) == "jnp"
        GLOBAL_FLAGS.set("enable_fusion_fallback", False)
        assert prefill_megakernel_mode(fused) == "interpret"
    finally:
        GLOBAL_FLAGS.set("enable_fusion_fallback", False)
        reset_prefill_fallback()
    assert prefill_megakernel_mode(fused) == "interpret"


# ---------------------------------------------------------------------------
# engine: fused == unfused, bitwise, across the serving feature matrix
# ---------------------------------------------------------------------------

def test_engine_fused_prefill_token_identical_fp_and_int8(deep_model):
    prompts = _prompts(deep_model, [3, 5, 24], seed=11)
    for kw in ({}, {"quantized_mode": "weight_only_int8",
                    "kv_cache_dtype": "int8"}):
        for scope in (None, "model"):
            merged = dict(kw, chunk_size=8, megakernel_scope=scope)
            ref, _ = _run_engine(deep_model, prompts, **merged)
            out, eng = _run_engine(deep_model, prompts,
                                   prefill_megakernel="fused", **merged)
            assert out == ref, (kw, scope)
            assert eng.prefill_megakernel == "fused"
            assert eng.decode_cache_size() == 1   # still ONE ragged trace
    snap = eng.metrics_snapshot()
    assert snap["prefill_megakernel"] == "fused"
    assert snap["prefill_megakernel_mode"] in ("jnp", "interpret",
                                               "pallas")


def test_engine_fused_prefill_chunk_boundary_step_budget(deep_model):
    """A pinned step_token_budget forces chunk boundaries mid-prompt
    (and mid-STEP packing changes): every boundary placement must stay
    token-identical, with spec-decode rows sharing the packed step."""
    prompts = _prompts(deep_model, [16, 24, 3], seed=19)
    # the budget is the binding chunker here (43 packed prompt tokens
    # vs a 32/40-token step): boundaries move between the two runs.
    # 32 is also the spec floor: max_num_seqs x q_block-rounded drafts
    for budget in (32, 40):
        kw = dict(chunk_size=32, step_token_budget=budget,
                  draft_model=deep_model, spec_tokens=2)
        ref, _ = _run_engine(deep_model, prompts, **kw)
        out, eng = _run_engine(deep_model, prompts,
                               prefill_megakernel="fused", **kw)
        assert out == ref, budget
        assert eng.metrics_snapshot()["prefill_chunks"] >= 3


def test_engine_fused_prefill_preemption_and_prefix_fork(deep_model):
    """Page-pressure preemption + prefix forks (shared pages, CoW
    tails) behave identically under the fused prefill bodies."""
    prefix = _prompts(deep_model, [16], seed=13)[0]
    tails = _prompts(deep_model, [2, 3], seed=14)

    def run(pk):
        eng = LLMEngine(deep_model, max_len=64, page_size=4,
                        max_num_seqs=4, num_pages=28, chunk_size=32,
                        prefill_megakernel=pk)
        donor = eng.add_request(prefix, max_new_tokens=8)
        eng.step(); eng.step()
        rids = [donor] + [eng.add_request(prefix + t, max_new_tokens=8)
                          for t in tails]
        outs = eng.run(max_steps=500)
        return [outs[r].token_ids for r in rids], eng

    ref, _ = run("unfused")
    out, eng = run("fused")
    assert out == ref
    assert eng.prefill_megakernel == "fused"


def test_engine_fused_prefill_prefetch_overlap_gate(deep_model):
    """The two-tier KVPrefetcher under fused prefill: over-capacity HBM
    + host arena serves token-identically with prefetch hits landing
    and ZERO steady-state stalls."""
    prompts = _prompts(deep_model, [6, 8, 40, 44], seed=17)
    kw = dict(max_new=16, num_pages=16, host_kv_pages=64, chunk_size=16)
    ref, _ = _run_engine(deep_model, prompts, **kw)
    out, eng = _run_engine(deep_model, prompts,
                           prefill_megakernel="fused", **kw)
    assert out == ref
    snap = eng.metrics_snapshot()
    assert snap["kv_spills"] > 0, "not over capacity: gate is vacuous"
    assert snap["kv_prefetch_hits"] > 0
    assert snap["kv_prefetch_stalls"] == 0


def test_engine_fused_prefill_int4_falls_back_honestly(deep_model):
    """int4 weights have no fused geometry: the ctor downgrades to
    unfused and reports it, rather than tracing a body it can't fuse."""
    eng = LLMEngine(deep_model, max_len=32, page_size=4,
                    quantized_mode="weight_only_int4",
                    prefill_megakernel="fused")
    assert eng.prefill_megakernel == "unfused"
    assert eng.metrics_snapshot()["prefill_megakernel"] == "unfused"


# ---------------------------------------------------------------------------
# the compiled ragged step gets structurally cheaper
# ---------------------------------------------------------------------------

def test_engine_fused_ragged_step_compiles_smaller(deep_model):
    eu = LLMEngine(deep_model, max_len=64, page_size=8, max_num_seqs=4,
                   megakernel_scope="model")
    ef = LLMEngine(deep_model, max_len=64, page_size=8, max_num_seqs=4,
                   megakernel_scope="model", prefill_megakernel="fused")
    cu = fusion_stats(eu.ragged_step_hlo())
    cf = fusion_stats(ef.ragged_step_hlo())
    assert cf["fusion_count"] < cu["fusion_count"], (cf, cu)
    assert cf["kernel_count"] < cu["kernel_count"], (cf, cu)


def test_generator_prefill_lowering_collapses(deep_model):
    for scope in (None, "model"):
        s = launch_stats(Generator(deep_model, max_len=64,
                                   megakernel_scope=scope)
                         .prefill_lowering(), num_layers=3)
        assert s["layer_body_sites"] == 3 and not s["collapsed"]
        s = launch_stats(Generator(deep_model, max_len=64,
                                   megakernel_scope=scope,
                                   prefill_megakernel="fused")
                         .prefill_lowering(), num_layers=3)
        assert s["layer_body_sites"] == 1 and s["collapsed"]


def test_generator_fused_prefill_token_identical(deep_model):
    prompt = _prompts(deep_model, [9], seed=3)[0]
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    for kw in (dict(temperature=0.0),
               dict(temperature=0.8, top_k=13, seed=3)):
        for gkw in ({}, {"megakernel_scope": "model"},
                    {"paged": True, "page_size": 8}):
            ref = Generator(deep_model, max_len=64, **gkw).generate(
                ids, max_new_tokens=10, **kw).numpy()
            out = Generator(deep_model, max_len=64,
                            prefill_megakernel="fused", **gkw).generate(
                ids, max_new_tokens=10, **kw).numpy()
            assert (out == ref).all(), (kw, gkw)


# ---------------------------------------------------------------------------
# mixed_launch_stats (satellite 1): heterogeneous-body accounting
# ---------------------------------------------------------------------------

def _program(markers):
    lines = ["module @jit_step {"]
    lines += ['  %x = "stablehlo.rsqrt"(%a) : (f32) -> f32'] * markers
    lines += ['  %y = "stablehlo.add"(%a, %b) : (f32, f32) -> f32', "}"]
    return "\n".join(lines)


def test_mixed_launch_stats_unique_decomposition():
    # L=3: prefill collapsed (1 site x 2 markers) + decode unrolled
    # (3 sites x 3 markers) + 1 overhead marker = 12
    s = mixed_launch_stats(_program(12), num_layers=3,
                           kinds={"prefill": 2, "decode": 3})
    assert s["marker_count"] == 12
    assert s["sites"] == {"prefill": 1, "decode": 3}
    assert s["total_body_sites"] == 4
    assert s["launches_per_token"] == 4.0
    assert not s["collapsed"]
    # both collapsed: 2 + 3 + 1 = 6, amortized over a 4-token chunk
    s = mixed_launch_stats(_program(6), num_layers=3,
                           kinds={"prefill": 2, "decode": 3},
                           tokens_per_invocation=4)
    assert s["sites"] == {"prefill": 1, "decode": 1}
    assert s["launches_per_token"] == 0.5
    assert s["collapsed"]


def test_mixed_launch_stats_refuses_to_fabricate():
    # ambiguous at L=2: 2a + 2b = 4 solves as (1,1), (0,2) and (2,0)
    with pytest.raises(ValueError, match="do not decompose"):
        mixed_launch_stats(_program(5), num_layers=2,
                           kinds={"prefill": 2, "decode": 2})
    # exclusive=True pins every kind to a live site {1, L}: unique
    s = mixed_launch_stats(_program(5), num_layers=2,
                           kinds={"prefill": 2, "decode": 2},
                           exclusive=True)
    assert s["sites"] == {"prefill": 1, "decode": 1}
    assert s["collapsed"]
    # no decomposition at all: odd budget over even marker counts
    with pytest.raises(ValueError, match="do not decompose"):
        mixed_launch_stats(_program(4), num_layers=2,
                           kinds={"prefill": 2, "decode": 2})


def test_engine_launch_stats_mixed_kinds(deep_model):
    """The engine's ragged step has ONE unified body kind (prefill and
    decode rows share it): kinds={'ragged': 2} must reproduce the
    homogeneous accounting at both scopes."""
    el = LLMEngine(deep_model, max_len=32, page_size=4)
    em = LLMEngine(deep_model, max_len=32, page_size=4,
                   megakernel_scope="model")
    sl = el.launch_stats(kinds={"ragged": 2})
    sm = em.launch_stats(kinds={"ragged": 2})
    assert sl["sites"] == {"ragged": 3} and not sl["collapsed"]
    assert sm["sites"] == {"ragged": 1} and sm["collapsed"]
    assert sm["launches_per_token"] == 1.0


# ---------------------------------------------------------------------------
# autotune key provenance (satellite 2)
# ---------------------------------------------------------------------------

def test_autotune_key_separates_prefill_geometry(monkeypatch):
    """Prefill tunings must never share a cache line across q_block,
    scan scope or stacked depth: the key carries all three."""
    import paddle_tpu.kernels.autotune as at
    (layer, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens,
     kw) = _layer_fixture()
    fused = fuse_layer_weights(layer)
    seen = []
    monkeypatch.setattr(at, "autotune_enabled", lambda: True)

    def record(key, requested, candidates, build_fn, traced=False):
        seen.append(key)
        return requested
    monkeypatch.setattr(at, "pick_cached", record)

    args = (fused, h, Kp, Vp, tbls, pre, q_starts, q_lens, kv_lens)
    fused_prefill_layer(*args, interpret=True, **kw)
    fused_prefill_layer(*args, interpret=True, scope="model",
                        num_layers=3, **kw)
    fused_prefill_layer(*args, interpret=True, scope="model",
                        num_layers=5, **kw)
    kw2 = dict(kw, q_block=16)
    fused_prefill_layer(*args, interpret=True, **kw2)
    assert len(seen) == 4
    assert len(set(seen)) == 4, seen
    assert all(k[0] == "prefill_megakernel" for k in seen)
    assert seen[0][-2:] == ("layer", 1)
    assert seen[1][-2:] == ("model", 3)
    assert seen[2][-2:] == ("model", 5)
    assert seen[3][-3:] == (16, "layer", 1)
    # everything BUT the provenance suffix is the same geometry
    assert seen[0][:-2] == seen[1][:-2] == seen[2][:-2]
    assert seen[0][:-3] == seen[3][:-3] and seen[0][-3] == 8


# ---------------------------------------------------------------------------
# prefill_launches + span attribution (satellite 6)
# ---------------------------------------------------------------------------

def test_prefill_launches_counter_and_span_attribution(deep_model):
    """One launch per step that served >=1 prefill-chunk row — the
    launches-per-chunk headline's numerator — and every prefill_chunk
    span says whether the fused path served it."""
    prompts = _prompts(deep_model, [5, 24], seed=23)

    def run(pk):
        tracer = RequestTracer()
        eng = LLMEngine(deep_model, max_len=64, page_size=4,
                        max_num_seqs=4, chunk_size=8, tracer=tracer,
                        prefill_megakernel=pk)
        rids = [eng.add_request(p, max_new_tokens=4) for p in prompts]
        eng.run(max_steps=200)
        return eng, tracer, rids

    eng, tracer, rids = run("fused")
    snap = eng.metrics_snapshot()
    # the 24-token prompt chunks at chunk_size=8: >=3 chunks but the
    # chunks of ONE step share ONE launch
    assert snap["prefill_chunks"] >= 4
    assert 1 <= snap["prefill_launches"] <= snap["prefill_chunks"]
    assert snap["prefill_launches"] <= snap["decode_steps"]
    spans = [d for r in rids for _, k, d in tracer.spans(r)
             if k == "prefill_chunk"]
    assert spans and all(d["fused"] is True for d in spans)

    eng, tracer, rids = run("unfused")
    assert eng.metrics_snapshot()["prefill_launches"] >= 1
    spans = [d for r in rids for _, k, d in tracer.spans(r)
             if k == "prefill_chunk"]
    assert spans and all(d["fused"] is False for d in spans)
