"""Decode megakernel + on-device burst loop gates.

The tentpole contracts (kernels/decode_megakernel.py,
models/generation.py, serving/engine.py):

- the fused decode-layer kernel (rms_norm -> qkv -> rope -> paged
  attention -> o-proj -> residual -> rms_norm -> mlp -> residual in ONE
  Pallas launch) matches its jnp fallback in every variant — fp / int8
  weights, fp / int8 KV pages, self-kv and append-first modes;
- burst mode (the jitted ``lax.while_loop`` token loop) is greedy
  token-IDENTICAL to the per-token path — through ``Generator.generate``
  and through the serving engine with chunked prefill, prefix forks and
  int8 KV live — and ``burst_tokens=1`` IS the per-token path;
- the host-dispatch gate: a generation burst of N tokens costs O(1)
  host dispatches (vs >= N per-token) — dispatches scale with
  ceil(tokens / burst), not tokens;
- the segmented int8 append is bitwise the single-token append for
  decode rows and stays within one rounding step of the sequential
  chunk walk it replaced;
- ``FLAGS_decode_burst_tokens`` validates through the flags on_set
  rollback path.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.flags import GLOBAL_FLAGS, set_flags
from paddle_tpu.kernels.decode_megakernel import (_reference_layer,
                                                  fused_decode_layer,
                                                  megakernel_mode)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config, Generator
from paddle_tpu.models.generation import host_dispatch_count
from paddle_tpu.serving import LLMEngine


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=64,
                            intermediate_size=128, num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _prompts(model, lengths, seed=0):
    rng = np.random.RandomState(seed)
    v = model.config.vocab_size
    return [rng.randint(0, v, (n,)).tolist() for n in lengths]


def _reference_tokens(model, prompt, n, max_len=64, eos=None):
    gen = Generator(model, max_len=max_len)
    out = gen.generate(paddle.to_tensor(np.asarray(prompt)[None],
                                        dtype="int64"),
                       max_new_tokens=n, temperature=0.0,
                       eos_token_id=eos, burst_tokens=1).numpy()
    return out[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# fused layer kernel vs fallback
# ---------------------------------------------------------------------------

def _layer_fixture(seed=0, R=4, D=64, H=4, Hkv=2, dh=16, F=96, PPS=6,
                   ps=4, P=12):
    rng = np.random.default_rng(seed)

    def arr(*s):
        return jnp.asarray(rng.standard_normal(s).astype(np.float32) * 0.3)

    layer = {"ln1": arr(D) + 1.0, "ln2": arr(D) + 1.0,
             "q": arr(D, H * dh), "k": arr(D, Hkv * dh),
             "v": arr(D, Hkv * dh), "o": arr(H * dh, D),
             "gate": arr(D, F), "up": arr(D, F), "down": arr(F, D)}
    h = arr(R, D)
    Kp, Vp = arr(Hkv, P, ps, dh), arr(Hkv, P, ps, dh)
    tbls = jnp.asarray(rng.integers(1, P, (R, PPS)), jnp.int32)
    # decode row, fresh row (self-token only), mid-page, page-crossing
    kv_lens = jnp.asarray([5, 1, 9, 17], jnp.int32)
    kw = dict(eps=1e-6, theta=10000.0, num_heads=H)
    return layer, h, Kp, Vp, tbls, kv_lens, kw


@pytest.mark.parametrize("self_kv", [True, False])
def test_fused_layer_kernel_matches_fallback(self_kv):
    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    ref = _reference_layer(layer, h, Kp, Vp, tbls, kv_lens,
                           self_kv=self_kv, k_scales=None, v_scales=None,
                           **kw)
    out = fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens,
                             self_kv=self_kv, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-4, atol=1e-4)
    if self_kv:
        # the returned append payload (roped k, v) must be exact: the
        # caller scatters it into the pool
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out[2]), np.asarray(ref[2]),
                                   rtol=1e-5, atol=1e-5)
    else:
        assert out[1] is None and out[2] is None


def test_fused_layer_int8_kv_pages():
    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    rng = np.random.default_rng(3)
    Hkv, P = Kp.shape[0], Kp.shape[1]
    ks = jnp.asarray(np.abs(rng.standard_normal((Hkv, P))) * 0.01 + 0.005,
                     jnp.float32)
    vs = jnp.asarray(np.abs(rng.standard_normal((Hkv, P))) * 0.01 + 0.005,
                     jnp.float32)
    Kq = jnp.clip(jnp.round(Kp / ks[:, :, None, None]), -127, 127) \
        .astype(jnp.int8)
    Vq = jnp.clip(jnp.round(Vp / vs[:, :, None, None]), -127, 127) \
        .astype(jnp.int8)
    ref = _reference_layer(layer, h, Kq, Vq, tbls, kv_lens, self_kv=False,
                           k_scales=ks, v_scales=vs, **kw)
    out = fused_decode_layer(layer, h, Kq, Vq, tbls, kv_lens,
                             self_kv=False, interpret=True, k_scales=ks,
                             v_scales=vs, **kw)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-4, atol=1e-4)


def test_fused_layer_int8_weights_dequant_prologue():
    from paddle_tpu.quantization.low_bit import quantize_params
    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    D = h.shape[1]
    qp = quantize_params({"embed": jnp.zeros((8, D), jnp.float32),
                          "norm": jnp.ones((D,), jnp.float32),
                          "layers": [layer]}, "weight_only_int8")
    qlayer = qp["layers"][0]
    ref = _reference_layer(qlayer, h, Kp, Vp, tbls, kv_lens, self_kv=True,
                           k_scales=None, v_scales=None, **kw)
    out = fused_decode_layer(qlayer, h, Kp, Vp, tbls, kv_lens,
                             self_kv=True, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                               rtol=1e-4, atol=1e-4)


def test_fused_layer_int4_weights_take_the_fallback():
    """int4 (and mixed) layouts must run the jnp fallback, not die in
    the kernel's operand assembly."""
    from paddle_tpu.quantization.low_bit import quantize_params
    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    D = h.shape[1]
    qp = quantize_params({"embed": jnp.zeros((8, D), jnp.float32),
                          "norm": jnp.ones((D,), jnp.float32),
                          "layers": [layer]}, "weight_only_int4")
    out = fused_decode_layer(qp["layers"][0], h, Kp, Vp, tbls, kv_lens,
                             self_kv=True, interpret=True, **kw)
    assert np.isfinite(np.asarray(out[0])).all()


def test_head_group_split_matches(monkeypatch):
    """The autotuned kv-head group split (G=2) computes the same layer
    as the default single group."""
    from paddle_tpu.kernels.autotune import get_autotuner
    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    base = fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens,
                              self_kv=True, interpret=True, **kw)
    tuner = get_autotuner()
    key = tuner._key(("decode_megakernel", h.shape[0], h.shape[1],
                      kw["num_heads"], Kp.shape[0], Kp.shape[3],
                      tbls.shape[1], Kp.shape[2], "fp", True, False,
                      "layer", 1))
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "1")
    tuner.cache[key] = {"head_groups": 2}
    try:
        split = fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens,
                                   self_kv=True, interpret=True, **kw)
    finally:
        tuner.cache.pop(key, None)
    np.testing.assert_allclose(np.asarray(split[0]), np.asarray(base[0]),
                               rtol=1e-5, atol=1e-5)


def test_megakernel_mode_reports_environment(monkeypatch):
    assert megakernel_mode() == "jnp"          # CPU container, unforced
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    assert megakernel_mode() == "interpret"


def test_megakernel_mode_never_fabricates_for_fallback_weights(
        tiny_model, monkeypatch):
    """Regression: int4 (and mixed) layouts run the jnp fallback on
    every backend — the reported mode (and the bench field riding it)
    must say so even when the environment would select a kernel."""
    from paddle_tpu.quantization.low_bit import quantize_params
    from paddle_tpu.models.generation import extract_params
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    q4 = quantize_params(extract_params(tiny_model), "weight_only_int4")
    assert megakernel_mode(q4["layers"][0]) == "jnp"
    q8 = quantize_params(extract_params(tiny_model), "weight_only_int8")
    assert megakernel_mode(q8["layers"][0]) == "interpret"
    eng = LLMEngine(tiny_model, max_len=32, page_size=4,
                    quantized_mode="weight_only_int4", burst_tokens=4)
    assert eng.metrics_snapshot()["megakernel_mode"] == "jnp"


def test_megakernel_mode_honors_pinned_interpret(tiny_model):
    """An explicit LLMEngine(interpret=True) pins the burst megakernel
    to the interpreter — the snapshot must say so (and interpret=False
    off-TPU must say jnp), not echo the environment."""
    e1 = LLMEngine(tiny_model, max_len=32, page_size=4, burst_tokens=4,
                   interpret=True)
    assert e1.metrics_snapshot()["megakernel_mode"] == "interpret"
    e2 = LLMEngine(tiny_model, max_len=32, page_size=4, burst_tokens=4,
                   interpret=False)
    assert e2.metrics_snapshot()["megakernel_mode"] == "jnp"


# ---------------------------------------------------------------------------
# Generator burst mode
# ---------------------------------------------------------------------------

def test_generator_burst_greedy_identical_and_dispatch_gate(tiny_model):
    prompt = _prompts(tiny_model, [5], seed=0)[0]
    gen = Generator(tiny_model, max_len=64)
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    c0 = host_dispatch_count()
    ref = gen.generate(ids, max_new_tokens=12, burst_tokens=1).numpy()
    per_token = host_dispatch_count() - c0
    c0 = host_dispatch_count()
    out = gen.generate(ids, max_new_tokens=12, burst_tokens=4).numpy()
    burst = host_dispatch_count() - c0
    assert (out == ref).all(), "burst diverged from the per-token loop"
    # >= N dispatches per-token (prefill + 11 decodes) vs prefill + 3
    assert per_token >= 12
    assert burst <= 1 + -(-11 // 4), (per_token, burst)


def test_generator_burst_dispatches_independent_of_tokens(tiny_model):
    """THE gate: at a fixed burst length, dispatches scale with
    ceil(tokens / burst), not tokens."""
    prompt = _prompts(tiny_model, [4], seed=1)[0]
    gen = Generator(tiny_model, max_len=64)
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")

    def dispatches(n, burst):
        c0 = host_dispatch_count()
        gen.generate(ids, max_new_tokens=n, burst_tokens=burst)
        return host_dispatch_count() - c0

    assert dispatches(20, 32) == dispatches(5, 32) == 2  # prefill + 1 burst
    assert dispatches(20, 1) >= 20


def test_generator_burst_sampling_draws_identical(tiny_model):
    """The burst body splits the PRNG key exactly like the host loop, so
    even temperature>0 sampling is draw-for-draw identical."""
    prompt = _prompts(tiny_model, [5], seed=2)[0]
    gen = Generator(tiny_model, max_len=64)
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    a = gen.generate(ids, max_new_tokens=10, temperature=0.8, seed=3,
                     burst_tokens=1).numpy()
    b = gen.generate(ids, max_new_tokens=10, temperature=0.8, seed=3,
                     burst_tokens=4).numpy()
    assert (a == b).all()


def test_generator_burst_eos_mid_burst_in_batch(tiny_model):
    """Two rows, one hits EOS mid-burst: the finished row pads eos (the
    per-token convention), the live row keeps generating, and the output
    truncates at the same step as the per-token loop."""
    prompts = _prompts(tiny_model, [5, 5], seed=4)
    ids = paddle.to_tensor(np.asarray(prompts), dtype="int64")
    gen = Generator(tiny_model, max_len=64)
    probe = gen.generate(ids, max_new_tokens=12, burst_tokens=1).numpy()
    eos = int(probe[0, 5 + 3])               # row 0 emits it mid-burst
    ref = gen.generate(ids, max_new_tokens=12, eos_token_id=eos,
                       burst_tokens=1).numpy()
    out = gen.generate(ids, max_new_tokens=12, eos_token_id=eos,
                       burst_tokens=5).numpy()
    assert ref.shape == out.shape and (ref == out).all()


def test_generator_burst_prefill_token_already_eos(tiny_model):
    """Regression: when the PREFILL-sampled token is already eos, the
    per-token loop still runs one decode iteration (its finished.all()
    break sits after the append) and emits one eos pad — the burst
    path must match in shape and content."""
    prompt = _prompts(tiny_model, [5], seed=6)[0]
    gen = Generator(tiny_model, max_len=64)
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    probe = gen.generate(ids, max_new_tokens=4, burst_tokens=1).numpy()
    eos = int(probe[0, 5])                   # the first generated token
    ref = gen.generate(ids, max_new_tokens=8, eos_token_id=eos,
                       burst_tokens=1).numpy()
    out = gen.generate(ids, max_new_tokens=8, eos_token_id=eos,
                       burst_tokens=4).numpy()
    assert ref.shape == out.shape and (ref == out).all()
    assert ref.shape[1] == 5 + 2             # eos + one pad, then stop


def test_generator_burst_tokens_1_is_the_per_token_path(tiny_model):
    """burst_tokens=1 must BE the existing per-token path (bit-identical
    by construction), including its dispatch count."""
    prompt = _prompts(tiny_model, [5], seed=5)[0]
    gen = Generator(tiny_model, max_len=64)
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    c0 = host_dispatch_count()
    a = gen.generate(ids, max_new_tokens=8, burst_tokens=1).numpy()
    d1 = host_dispatch_count() - c0
    c0 = host_dispatch_count()
    b = gen.generate(ids, max_new_tokens=8).numpy()   # flag default = 1
    d2 = host_dispatch_count() - c0
    assert (a == b).all() and d1 == d2 == 8


# ---------------------------------------------------------------------------
# engine burst mode
# ---------------------------------------------------------------------------

def _run_engine(model, prompts, max_new=8, **kw):
    eng = LLMEngine(model, max_len=64, page_size=4, max_num_seqs=4, **kw)
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    outs = eng.run(max_steps=400)
    return [outs[r].token_ids for r in rids], eng


def test_engine_burst_token_identical_mixed_requests(tiny_model):
    """Burst engine == per-token engine == sequential Generator, with a
    chunked long prompt in the mix (bursts engage only once every row is
    caught up; chunks still ride the per-step ragged path)."""
    prompts = _prompts(tiny_model, [3, 5, 24], seed=11)
    ref, _ = _run_engine(tiny_model, prompts, chunk_size=8)
    out, eng = _run_engine(tiny_model, prompts, chunk_size=8,
                           burst_tokens=8)
    assert out == ref
    for p, toks in zip(prompts, out):
        assert toks == _reference_tokens(tiny_model, p, 8)
    snap = eng.metrics_snapshot()
    assert snap["burst_launches"] >= 1
    assert snap["prefill_chunks"] >= 3       # the 24-token prompt chunked
    assert snap["decode_cache_size"] == 1    # ragged gate unaffected


def test_engine_burst_int8_kv_token_identical(tiny_model):
    prompts = _prompts(tiny_model, [3, 6], seed=12)
    ref, _ = _run_engine(tiny_model, prompts, kv_cache_dtype="int8")
    out, eng = _run_engine(tiny_model, prompts, kv_cache_dtype="int8",
                           burst_tokens=4)
    assert out == ref
    assert eng.metrics_snapshot()["burst_launches"] >= 1


def test_engine_burst_with_prefix_forks_live(tiny_model):
    """Forked sequences (shared prefix pages, tail-page CoW) ride bursts
    token-identically."""
    prefix = _prompts(tiny_model, [16], seed=13)[0]
    tails = _prompts(tiny_model, [2, 3], seed=14)
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4,
                    chunk_size=32, burst_tokens=6)
    donor = eng.add_request(prefix, max_new_tokens=8)
    eng.step(); eng.step()
    rids = [eng.add_request(prefix + t, max_new_tokens=8) for t in tails]
    outs = eng.run(max_steps=400)
    assert eng.metrics_snapshot()["prefix_cache_hits"] == len(tails)
    assert eng.metrics_snapshot()["burst_launches"] >= 1
    assert outs[donor].token_ids == _reference_tokens(tiny_model, prefix, 8)
    for rid, t in zip(rids, tails):
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, prefix + t, 8)
    eng.pool.check_invariants()


def test_engine_burst_mid_burst_eos_of_one_row(tiny_model):
    """One row EOSes mid-burst: it finalizes with reason 'eos' at the
    same token as the per-token engine while the other row bursts on."""
    prompts = _prompts(tiny_model, [4, 6], seed=15)
    ref0 = _reference_tokens(tiny_model, prompts[0], 10)
    eos = ref0[3]                             # row 0 dies at token 4
    want0 = _reference_tokens(tiny_model, prompts[0], 10, eos=eos)
    want1 = _reference_tokens(tiny_model, prompts[1], 10)

    def run(burst):
        eng = LLMEngine(tiny_model, max_len=64, page_size=4,
                        max_num_seqs=4, burst_tokens=burst)
        r0 = eng.add_request(prompts[0], max_new_tokens=10,
                             eos_token_id=eos)
        r1 = eng.add_request(prompts[1], max_new_tokens=10)
        outs = eng.run(max_steps=300)
        return outs[r0], outs[r1]

    p0, p1 = run(1)
    b0, b1 = run(8)
    assert b0.token_ids == p0.token_ids == want0   # eos-truncated
    assert len(b0.token_ids) < 10, "row 0 must have died mid-burst"
    assert b0.finish_reason == p0.finish_reason == "eos"
    assert b1.token_ids == p1.token_ids == want1


def test_engine_host_dispatch_gate(tiny_model):
    """THE acceptance gate: a burst of N tokens costs O(1) host
    dispatches — dispatch count is flat in tokens generated at a fixed
    burst length, vs >= N on the per-token path."""
    prompt = _prompts(tiny_model, [4], seed=16)[0]

    def dispatches(max_new, burst):
        eng = LLMEngine(tiny_model, max_len=64, page_size=4,
                        max_num_seqs=4, burst_tokens=burst)
        eng.add_request(prompt, max_new_tokens=max_new)
        eng.run(max_steps=300)
        return eng.metrics_snapshot()["host_dispatches"]

    # per-token: >= one dispatch per generated token
    assert dispatches(20, 1) >= 20
    # burst: prefill step + ONE burst regardless of 5 or 20 tokens
    d20 = dispatches(20, 32)
    d5 = dispatches(5, 32)
    assert d20 == d5 == 2, (d5, d20)
    # and the snapshot exposes the bench probe's ratio
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4,
                    burst_tokens=32)
    eng.add_request(prompt, max_new_tokens=20)
    eng.run(max_steps=300)
    snap = eng.metrics_snapshot()
    assert snap["host_dispatches_per_token"] <= 0.15
    assert snap["burst_tokens"] == 32
    assert snap["megakernel_mode"] == "jnp"   # CPU container


def test_burst_plan_drops_rows_preempted_by_later_rows(tiny_model):
    """Regression: a later row's PoolExhausted retry can preempt an
    ALREADY-planned row — the burst plan must drop it (its pool entry
    is freed) instead of crashing _launch_burst with a KeyError, and
    the loop must still serve everyone token-identically.

    Prompts are page-aligned (8 tokens, ps=8) so the third row has ZERO
    slack in its owned pages — cap shrinking cannot save it and the
    preemption path must fire, with the latest-arrival victim being the
    already-planned second row."""
    prompts = _prompts(tiny_model, [8, 8, 8], seed=19)
    # pool too small for 3 rows' burst growth: planning preempts
    eng = LLMEngine(tiny_model, max_len=64, page_size=8, num_pages=6,
                    max_num_seqs=3, chunk_size=8, burst_tokens=8,
                    high_watermark=1.0)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run(max_steps=500)            # KeyError before the fix
    assert eng.metrics_snapshot()["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert outs[rid].status == "finished"
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, p, 8, max_len=64)
    eng.pool.check_invariants()


def test_burst_cap_shrinks_before_preempting(tiny_model):
    """Under pool pressure a row's burst cap shrinks to what its owned
    pages still hold instead of preempting a neighbor into a full
    re-prefill — this load is servable with ZERO preemptions."""
    prompts = _prompts(tiny_model, [5, 5], seed=20)
    # 3 usable pages, ps=8: both rows prefill into 1 page each; the
    # first burst-planned row claims the last free page, the second
    # must shrink its cap to its page slack (3 tokens), not preempt
    eng = LLMEngine(tiny_model, max_len=16, page_size=8, num_pages=4,
                    max_num_seqs=2, chunk_size=8, burst_tokens=8,
                    high_watermark=1.0)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run(max_steps=100)
    snap = eng.metrics_snapshot()
    assert snap["preemptions"] == 0, \
        "shrinkable burst caps must not preempt"
    assert snap["burst_launches"] >= 2
    for rid, p in zip(rids, prompts):
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, p, 8, max_len=64)
    eng.pool.check_invariants()


def test_engine_burst_respects_page_growth_and_preemption(tiny_model):
    """A starved pool under burst mode still preempts correctly and
    stays token-identical (the burst pre-claims pages; planning preempts
    exactly like the per-step path)."""
    prompts = _prompts(tiny_model, [6, 7, 9], seed=17)
    eng = LLMEngine(tiny_model, max_len=32, page_size=4, num_pages=9,
                    max_num_seqs=3, burst_tokens=4, high_watermark=1.0)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run(max_steps=500)
    assert eng.metrics_snapshot()["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, p, 8, max_len=64)
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.capacity


# ---------------------------------------------------------------------------
# segmented int8 append
# ---------------------------------------------------------------------------

def _seq_walk_reference(Pp, Ps, chunk, tbls, q_starts, q_lens, kv_lens,
                        ps, pps):
    """The replaced per-token chunk walk, as the oracle."""
    from paddle_tpu.serving.engine import _quantized_append
    rows = jnp.arange(tbls.shape[0])
    for i in range(int(jnp.max(q_lens))):
        live = i < q_lens
        flat = jnp.clip(q_starts + i, 0, chunk.shape[1] - 1)
        pos = jnp.maximum(kv_lens - q_lens + i, 0)
        page = jnp.where(live, tbls[rows, jnp.clip(pos // ps, 0, pps - 1)],
                         0)
        Pp, Ps = _quantized_append(Pp, Ps, chunk[:, flat], page, pos % ps,
                                   ps, live)
    return Pp, Ps


def _append_fixture(q_lens, kv_lens, seed=0, Hkv=2, d=8, ps=4, pps=4,
                    P=10, T=16):
    rng = np.random.default_rng(seed)
    Pp = jnp.zeros((Hkv, P, ps, d), jnp.int8)
    Ps = jnp.zeros((Hkv, P), jnp.float32)
    chunk = jnp.asarray(rng.standard_normal((Hkv, T, d)), jnp.float32)
    R = len(q_lens)
    tbls = jnp.asarray(
        np.arange(1, 1 + R * pps).reshape(R, pps), jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    kv_lens = jnp.asarray(kv_lens, jnp.int32)
    q_starts = jnp.asarray(np.concatenate(
        [[0], np.cumsum(np.asarray(q_lens))[:-1]]), jnp.int32)
    return Pp, Ps, chunk, tbls, q_starts, q_lens, kv_lens


def test_segmented_append_decode_rows_equal_single_token():
    """q_len=1 rows (every decode launch): the segmented append is the
    single-token running-amax append — same scales (to compiled-vs-
    eager float variance, ~1ulp: the segmented body compiles under
    fori_loop, the walk runs eager) and identical stored int8."""
    from paddle_tpu.serving.engine import _segmented_quant_append
    Pp, Ps, chunk, tbls, q_starts, q_lens, kv_lens = _append_fixture(
        q_lens=[1, 1, 1], kv_lens=[1, 6, 9])
    a_p, a_s = _segmented_quant_append(Pp, Ps, chunk, tbls, q_starts,
                                       q_lens, kv_lens, 4, 4, 8)
    b_p, b_s = _seq_walk_reference(Pp, Ps, chunk, tbls, q_starts, q_lens,
                                   kv_lens, 4, 4)
    np.testing.assert_allclose(np.asarray(a_s), np.asarray(b_s),
                               rtol=1e-6, atol=0)
    assert (np.abs(np.asarray(a_p, np.int32)
                   - np.asarray(b_p, np.int32)) <= 1).all()
    assert (np.asarray(a_p) == np.asarray(b_p)).mean() > 0.99


def test_segmented_append_chunk_within_one_rounding_step_of_walk():
    """Multi-token chunks: same final scales as the sequential walk, and
    every stored value within one quantization step (the walk
    double-rounds early tokens through intermediate scales; the
    segmented append quantizes once at the final scale)."""
    from paddle_tpu.serving.engine import _segmented_quant_append
    Pp, Ps, chunk, tbls, q_starts, q_lens, kv_lens = _append_fixture(
        q_lens=[7, 3, 1], kv_lens=[9, 3, 5], seed=1)
    a_p, a_s = _segmented_quant_append(Pp, Ps, chunk, tbls, q_starts,
                                       q_lens, kv_lens, 4, 4, 8)
    b_p, b_s = _seq_walk_reference(Pp, Ps, chunk, tbls, q_starts, q_lens,
                                   kv_lens, 4, 4)
    np.testing.assert_allclose(np.asarray(a_s), np.asarray(b_s),
                               rtol=1e-6, atol=1e-8)
    # dequantized disagreement bounded by one step of the page's scale
    da = np.asarray(a_p, np.float32) * np.asarray(a_s)[:, :, None, None]
    db = np.asarray(b_p, np.float32) * np.asarray(b_s)[:, :, None, None]
    step = np.asarray(a_s)[:, :, None, None]
    assert (np.abs(da - db) <= step + 1e-7).all()


def test_engine_int8_chunked_prefill_still_agrees(tiny_model):
    """The segmented append through the real engine: int8 chunked
    prefill still top-1-agrees with the fp engine (the PR 5/6 gate)."""
    prompts = _prompts(tiny_model, [9, 13], seed=18)
    fp, _ = _run_engine(tiny_model, prompts, chunk_size=4)
    q8, _ = _run_engine(tiny_model, prompts, chunk_size=4,
                        kv_cache_dtype="int8")
    flat_fp = [t for s in fp for t in s]
    flat_q8 = [t for s in q8 for t in s]
    agree = sum(a == b for a, b in zip(flat_fp, flat_q8)) / len(flat_fp)
    assert agree >= 0.8, (fp, q8)


# ---------------------------------------------------------------------------
# pinned-page LRU prefix cache (engine level; pool gates in
# test_serving_kv_pool.py)
# ---------------------------------------------------------------------------

def test_pinned_prefix_survives_release_and_reforks(tiny_model):
    """Repeated cold prompts: after the only sharer finishes and is
    released, the pinned chain re-forks the prompt instead of
    re-prefilling it (PR 6's named follow-up)."""
    P = _prompts(tiny_model, [16], seed=21)[0]     # 4 full pages, ps=4
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4,
                    chunk_size=32, pinned_prefix_pages=8)
    r1 = eng.add_request(P, max_new_tokens=4)
    eng.run(max_steps=100)
    eng.release(r1)
    assert eng.pool.pinned_pages == 4              # chain outlived r1
    eng.pool.check_invariants()
    chunks_before = eng.metrics.prefill_chunks.value
    r2 = eng.add_request(P, max_new_tokens=4)
    outs = eng.run(max_steps=100)
    snap = eng.metrics_snapshot()
    assert snap["pinned_prefix_hits"] == 1
    # only the unshared tail (the last prompt token) re-prefilled
    assert eng.metrics.prefill_chunks.value - chunks_before == 1
    assert outs[r2].token_ids == _reference_tokens(tiny_model, P, 4)
    eng.pool.check_invariants()


def test_pinned_budget_zero_keeps_legacy_behavior(tiny_model):
    """Default engines pin nothing: pages all return to the free list
    when the last sharer leaves (the pre-existing pool gates)."""
    P = _prompts(tiny_model, [16], seed=22)[0]
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4)
    rid = eng.add_request(P, max_new_tokens=4)
    eng.run(max_steps=100)
    eng.release(rid)
    assert eng.pool.pinned_pages == 0
    assert eng.pool.free_pages == eng.pool.capacity


def test_pinned_chains_yield_to_demand(tiny_model):
    """Pinned pages are cache, not demand: when real traffic needs the
    pool, LRU chains are evicted instead of raising PoolExhausted or
    starving admission."""
    P = _prompts(tiny_model, [16], seed=23)[0]
    # pool of 12 usable pages; the pinned chain holds 4
    eng = LLMEngine(tiny_model, max_len=32, page_size=4, num_pages=13,
                    max_num_seqs=3, chunk_size=16, pinned_prefix_pages=4)
    r1 = eng.add_request(P, max_new_tokens=4)
    eng.run(max_steps=100)
    eng.release(r1)
    assert eng.pool.pinned_pages == 4
    # three 8-token requests need 3*ceil(16/4)=... > 8 free pages: the
    # chain must be evicted to serve them
    prompts = _prompts(tiny_model, [8, 8, 8], seed=24)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run(max_steps=400)
    for rid, p in zip(rids, prompts):
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, p, 8, max_len=64)
    assert eng.pool.pin_evictions >= 1
    eng.pool.check_invariants()


# ---------------------------------------------------------------------------
# FLAGS_decode_burst_tokens
# ---------------------------------------------------------------------------

def test_burst_flag_validates_via_on_set_rollback():
    old = GLOBAL_FLAGS.get("decode_burst_tokens")
    try:
        with pytest.raises(ValueError, match="decode_burst_tokens"):
            set_flags({"decode_burst_tokens": 0})
        # the rejecting on_set must leave the previous value in place
        assert GLOBAL_FLAGS.get("decode_burst_tokens") == old
        with pytest.raises(ValueError):
            set_flags({"FLAGS_decode_burst_tokens": -3})
        assert GLOBAL_FLAGS.get("decode_burst_tokens") == old
        set_flags({"decode_burst_tokens": 4})
        assert GLOBAL_FLAGS.get("decode_burst_tokens") == 4
    finally:
        GLOBAL_FLAGS.set("decode_burst_tokens", old)


def test_burst_flag_feeds_engine_and_generator_defaults(tiny_model):
    old = GLOBAL_FLAGS.get("decode_burst_tokens")
    try:
        set_flags({"decode_burst_tokens": 4})
        eng = LLMEngine(tiny_model, max_len=32, page_size=4)
        assert eng.burst_tokens == 4
        prompt = _prompts(tiny_model, [5], seed=25)[0]
        gen = Generator(tiny_model, max_len=64)
        ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
        c0 = host_dispatch_count()
        out = gen.generate(ids, max_new_tokens=9).numpy()   # flag default
        assert host_dispatch_count() - c0 == 1 + 2          # prefill + 2
        set_flags({"decode_burst_tokens": 1})
        ref = gen.generate(ids, max_new_tokens=9).numpy()
        assert (out == ref).all()
    finally:
        GLOBAL_FLAGS.set("decode_burst_tokens", old)


# ---------------------------------------------------------------------------
# whole-model scope (ISSUE 18): fused_decode_model + the engine scan
# ---------------------------------------------------------------------------

from paddle_tpu.kernels.decode_megakernel import (fused_decode_model,
                                                  megakernel_fallback_tripped,
                                                  reset_megakernel_fallback,
                                                  stack_layer_params)


@pytest.fixture(scope="module")
def deep_model():
    """A 3-layer micro model: deep enough that the layer loop's
    structure (unrolled vs scanned) is observable, small enough for the
    CPU tier."""
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=3, hidden_size=64,
                            intermediate_size=96, num_attention_heads=4,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _model_fixture(L=3, seed0=20):
    """L independent layer fixtures sharing one request geometry: the
    first fixture's h / tables / kv_lens, per-layer weights and pages."""
    layers, Kps, Vps = [], [], []
    h = tbls = kv_lens = kw = None
    for li in range(L):
        layer, h_i, Kp, Vp, tbls_i, kv_lens_i, kw_i = _layer_fixture(
            seed=seed0 + li)
        layers.append(layer)
        Kps.append(Kp)
        Vps.append(Vp)
        if li == 0:
            h, tbls, kv_lens, kw = h_i, tbls_i, kv_lens_i, kw_i
    return layers, h, jnp.stack(Kps), jnp.stack(Vps), tbls, kv_lens, kw


def _slot_append(tbls, kv_lens, ps):
    """The caller-owned pool write both scopes share: scatter each
    row's current (k, v) at its (page, offset) slot."""
    R = kv_lens.shape[0]
    page = tbls[jnp.arange(R), kv_lens // ps]
    off = kv_lens % ps
    slot = page * ps + off

    def append_fn(Kp, Vp, kc, vc):
        P = Kp.shape[1]
        kt, vt = jnp.transpose(kc, (1, 0, 2)), jnp.transpose(vc, (1, 0, 2))
        Kp = Kp.reshape(Kp.shape[0], P * ps, -1).at[:, slot].set(kt) \
            .reshape(Kp.shape[0], P, ps, -1)
        Vp = Vp.reshape(Vp.shape[0], P * ps, -1).at[:, slot].set(vt) \
            .reshape(Vp.shape[0], P, ps, -1)
        return Kp, Vp
    return append_fn


def test_fused_model_fp_self_kv_matches_layer_loop():
    """The scanned whole-model body == the python loop over
    fused_decode_layer with the same caller-owned appends: the collapse
    is a launch-count change, never a numerics change."""
    layers, h, Kst, Vst, tbls, kv_lens, kw = _model_fixture()
    ps = int(Kst.shape[3])
    append_fn = _slot_append(tbls, kv_lens, ps)

    href = h
    Kref = [Kst[li] for li in range(3)]
    Vref = [Vst[li] for li in range(3)]
    for li in range(3):
        href, kc, vc = fused_decode_layer(
            layers[li], href, Kref[li], Vref[li], tbls, kv_lens,
            self_kv=True, interpret=True, **kw)
        Kref[li], Vref[li] = append_fn(Kref[li], Vref[li], kc, vc)

    stacked = stack_layer_params(layers)
    hout, Kn, Vn, ksn, vsn = fused_decode_model(
        stacked, h, Kst, Vst, tbls, kv_lens, self_kv=True,
        interpret=True, append_fn=append_fn, **kw)
    assert ksn is None and vsn is None
    # the scan compiles (lax.scan is a primitive) while the reference
    # loop runs op-by-op, so tolerance-parity is the contract here;
    # BITWISE identity is gated at the engine level, where both scopes
    # run under the same jit
    np.testing.assert_allclose(np.asarray(hout), np.asarray(href),
                               rtol=1e-4, atol=1e-4)
    for li in range(3):
        np.testing.assert_allclose(np.asarray(Kn[li]),
                                   np.asarray(Kref[li]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(Vn[li]),
                                   np.asarray(Vref[li]),
                                   rtol=1e-4, atol=1e-4)


def test_fused_model_int8_weights_stack_and_match():
    """LayerStack-stacked QuantizedWeight layers (int8 payload + scales
    stacked leaf-wise) scan to the same result as the per-layer loop."""
    from paddle_tpu.quantization.low_bit import quantize_params
    layers, h, Kst, Vst, tbls, kv_lens, kw = _model_fixture(seed0=30)
    D = h.shape[1]
    qp = quantize_params({"embed": jnp.zeros((8, D), jnp.float32),
                          "norm": jnp.ones((D,), jnp.float32),
                          "layers": layers}, "weight_only_int8")
    qlayers = qp["layers"]
    ps = int(Kst.shape[3])
    append_fn = _slot_append(tbls, kv_lens, ps)

    href = h
    Kref = [Kst[li] for li in range(3)]
    Vref = [Vst[li] for li in range(3)]
    for li in range(3):
        href, kc, vc = fused_decode_layer(
            qlayers[li], href, Kref[li], Vref[li], tbls, kv_lens,
            self_kv=True, interpret=True, **kw)
        Kref[li], Vref[li] = append_fn(Kref[li], Vref[li], kc, vc)

    hout, Kn, Vn, _, _ = fused_decode_model(
        stack_layer_params(qlayers), h, Kst, Vst, tbls, kv_lens,
        self_kv=True, interpret=True, append_fn=append_fn, **kw)
    np.testing.assert_allclose(np.asarray(hout), np.asarray(href),
                               rtol=1e-4, atol=1e-4)
    for li in range(3):
        np.testing.assert_allclose(np.asarray(Kn[li]),
                                   np.asarray(Kref[li]),
                                   rtol=1e-4, atol=1e-4)


def test_fused_model_int8_kv_quant_append_matches_layer_loop():
    """The append-first int8-KV path: the scanned body's in-scan
    prologue (rms -> k/v proj -> rope) + quantized append + attention
    over the updated pages equals the per-layer sequence."""
    from paddle_tpu.models.generation import _rms_norm, _rope, _wmat
    from paddle_tpu.serving.engine import _quantized_append
    layers, h, Kst, Vst, tbls, kv_lens, kw = _model_fixture(seed0=40)
    rng = np.random.default_rng(9)
    L, Hkv, P, ps, dh = (int(Kst.shape[0]), int(Kst.shape[1]),
                         int(Kst.shape[2]), int(Kst.shape[3]),
                         int(Kst.shape[4]))
    R, D = h.shape
    scales = jnp.asarray(
        np.abs(rng.standard_normal((2, L, Hkv, P))) * 0.01 + 0.005,
        jnp.float32)
    Ksc, Vsc = scales[0], scales[1]
    Kq = jnp.clip(jnp.round(Kst / Ksc[:, :, :, None, None]),
                  -127, 127).astype(jnp.int8)
    Vq = jnp.clip(jnp.round(Vst / Vsc[:, :, :, None, None]),
                  -127, 127).astype(jnp.int8)
    page = tbls[jnp.arange(R), (kv_lens - 1) // ps]
    off = (kv_lens - 1) % ps
    live = jnp.ones((R,), bool)

    def quant_append_fn(Kp, Ks, Vp, Vs, kc, vc):
        Kp, Ks = _quantized_append(Kp, Ks, jnp.transpose(kc, (1, 0, 2)),
                                   page, off, ps, live)
        Vp, Vs = _quantized_append(Vp, Vs, jnp.transpose(vc, (1, 0, 2)),
                                   page, off, ps, live)
        return Kp, Ks, Vp, Vs

    pos = jnp.maximum(kv_lens - 1, 0)
    href = h
    Kref = [Kq[li] for li in range(L)]
    Vref = [Vq[li] for li in range(L)]
    Ksr = [Ksc[li] for li in range(L)]
    Vsr = [Vsc[li] for li in range(L)]
    for li in range(L):
        x = _rms_norm(href[None], layers[li]["ln1"], kw["eps"])[0]
        kc = _rope(_wmat(x, layers[li]["k"]).reshape(R, Hkv, dh)[None],
                   pos[None], kw["theta"], dh)[0]
        vc = _wmat(x, layers[li]["v"]).reshape(R, Hkv, dh)
        Kref[li], Ksr[li], Vref[li], Vsr[li] = quant_append_fn(
            Kref[li], Ksr[li], Vref[li], Vsr[li], kc, vc)
        href, _, _ = fused_decode_layer(
            layers[li], href, Kref[li], Vref[li], tbls, kv_lens,
            self_kv=False, interpret=True, k_scales=Ksr[li],
            v_scales=Vsr[li], **kw)

    hout, Kn, Vn, Ksn, Vsn = fused_decode_model(
        stack_layer_params(layers), h, Kq, Vq, tbls, kv_lens,
        self_kv=False, interpret=True, k_scales=Ksc, v_scales=Vsc,
        quant_append_fn=quant_append_fn, **kw)
    np.testing.assert_allclose(np.asarray(hout), np.asarray(href),
                               rtol=1e-4, atol=1e-4)
    for li in range(L):
        # int8 codes may flip one rounding step under compiled-vs-eager
        # float drift; the scale columns track to float tolerance
        assert np.abs(np.asarray(Kn[li], np.int32)
                      - np.asarray(Kref[li], np.int32)).max() <= 1
        np.testing.assert_allclose(np.asarray(Ksn[li]),
                                   np.asarray(Ksr[li]),
                                   rtol=1e-5, atol=1e-7)


def test_fused_model_argument_contract():
    layers, h, Kst, Vst, tbls, kv_lens, kw = _model_fixture()
    with pytest.raises(ValueError, match="append_fn"):
        fused_decode_model(stack_layer_params(layers), h, Kst, Vst,
                           tbls, kv_lens, self_kv=True, interpret=True,
                           **kw)
    with pytest.raises(ValueError, match="quant_append_fn"):
        fused_decode_model(stack_layer_params(layers), h, Kst, Vst,
                           tbls, kv_lens, self_kv=False, interpret=True,
                           **kw)
    with pytest.raises(ValueError):
        stack_layer_params([])


# ---------------------------------------------------------------------------
# engine + generator: layer-scope vs model-scope token identity
# ---------------------------------------------------------------------------

def test_generator_model_scope_token_identical(deep_model):
    prompt = _prompts(deep_model, [5], seed=0)[0]
    ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
    for kw in (dict(temperature=0.0),
               dict(temperature=0.8, top_k=13, seed=3)):
        for burst in (1, 4):
            ref = Generator(deep_model, max_len=64).generate(
                ids, max_new_tokens=10, burst_tokens=burst, **kw).numpy()
            out = Generator(deep_model, max_len=64,
                            megakernel_scope="model").generate(
                ids, max_new_tokens=10, burst_tokens=burst, **kw).numpy()
            assert (out == ref).all(), (kw, burst)


def test_engine_model_scope_token_identical_fp_and_int8(deep_model):
    prompts = _prompts(deep_model, [3, 5, 24], seed=11)
    for kw in ({}, {"quantized_mode": "weight_only_int8",
                    "kv_cache_dtype": "int8"}):
        for burst in ({}, {"burst_tokens": 4}):
            merged = dict(kw, chunk_size=8, **burst)
            ref, _ = _run_engine(deep_model, prompts, **merged)
            out, eng = _run_engine(deep_model, prompts,
                                   megakernel_scope="model", **merged)
            assert out == ref, (kw, burst)
            assert eng.megakernel_scope == "model"
    snap = eng.metrics_snapshot()
    assert snap["megakernel_scope"] == "model"
    assert snap["decode_cache_size"] == 1     # ragged gate unaffected


def test_engine_model_scope_spec_decode_identity(deep_model):
    """Spec-decode verification rounds ride the scanned ragged
    executable: drafts + rollbacks stay token-identical across scopes."""
    prompt = [5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7]

    def run(scope):
        eng = LLMEngine(deep_model, max_len=64, page_size=4,
                        max_num_seqs=2, draft_model=deep_model,
                        spec_tokens=2, megakernel_scope=scope)
        rid = eng.add_request(prompt, max_new_tokens=10)
        return eng.run(max_steps=300)[rid].token_ids, eng

    ref, _ = run("layer")
    out, eng = run("model")
    assert out == ref
    assert eng.decode_cache_size() == 1


def test_engine_model_scope_preemption_and_prefix_fork(deep_model):
    """Page-pressure preemption + prefix forks (shared pages, CoW
    tails) behave identically under the scanned step."""
    prefix = _prompts(deep_model, [16], seed=13)[0]
    tails = _prompts(deep_model, [2, 3], seed=14)

    def run(scope):
        eng = LLMEngine(deep_model, max_len=64, page_size=4,
                        max_num_seqs=4, num_pages=28, chunk_size=32,
                        megakernel_scope=scope)
        donor = eng.add_request(prefix, max_new_tokens=8)
        eng.step(); eng.step()
        rids = [donor] + [eng.add_request(prefix + t, max_new_tokens=8)
                          for t in tails]
        outs = eng.run(max_steps=500)
        return [outs[r].token_ids for r in rids], eng

    ref, _ = run("layer")
    out, eng = run("model")
    assert out == ref
    assert eng.metrics_snapshot()["megakernel_scope"] == "model"


def test_engine_model_scope_prefetch_overlap_gate(deep_model):
    """The two-tier KVPrefetcher must still overlap restores under the
    longer-running scanned step: over-capacity HBM + host arena at
    model scope serves token-identically to layer scope with prefetch
    hits landing and ZERO steady-state stalls."""
    prompts = _prompts(deep_model, [6, 8, 40, 44], seed=17)
    kw = dict(max_new=16, num_pages=16, host_kv_pages=64,
              chunk_size=16)
    ref, eref = _run_engine(deep_model, prompts, **kw)
    out, eng = _run_engine(deep_model, prompts,
                           megakernel_scope="model", **kw)
    assert out == ref
    snap = eng.metrics_snapshot()
    assert snap["kv_spills"] > 0, "not over capacity: gate is vacuous"
    assert snap["kv_prefetch_hits"] > 0
    assert snap["kv_prefetch_stalls"] == 0


# ---------------------------------------------------------------------------
# launch accounting: the collapse is structural, not asserted
# ---------------------------------------------------------------------------

def test_engine_launch_stats_collapse(deep_model):
    el = LLMEngine(deep_model, max_len=32, page_size=4)
    em = LLMEngine(deep_model, max_len=32, page_size=4,
                   megakernel_scope="model")
    sl, sm = el.launch_stats(), em.launch_stats()
    assert sl["layer_body_sites"] == 3 and not sl["collapsed"]
    assert sl["launches_per_token"] == 3.0
    assert sm["layer_body_sites"] == 1 and sm["collapsed"]
    assert sm["launches_per_token"] == 1.0


def test_engine_burst_launch_stats_collapse(deep_model):
    em = LLMEngine(deep_model, max_len=32, page_size=4, burst_tokens=4,
                   megakernel_scope="model")
    s = em.launch_stats(burst=True)
    assert s["collapsed"] and s["launches_per_token"] == 0.25
    el = LLMEngine(deep_model, max_len=32, page_size=4, burst_tokens=4)
    s = el.launch_stats(burst=True)
    assert not s["collapsed"] and s["launches_per_token"] == 0.75


def test_engine_launch_stats_int8_burst_body(deep_model):
    """The int8 burst body carries the pre-append prologue's extra
    rms_norm: launch_stats' markers_per_body accounting must decompose
    it rather than mis-divide."""
    em = LLMEngine(deep_model, max_len=32, page_size=4, burst_tokens=4,
                   quantized_mode="weight_only_int8",
                   kv_cache_dtype="int8", megakernel_scope="model")
    s = em.launch_stats(burst=True)
    assert s["collapsed"] and s["launches_per_token"] == 0.25
    sm = em.launch_stats()
    assert sm["collapsed"] and sm["launches_per_token"] == 1.0


# ---------------------------------------------------------------------------
# scope flag + autotune-key provenance + fallback honesty
# ---------------------------------------------------------------------------

def test_scope_flag_validates_via_on_set_rollback():
    old = GLOBAL_FLAGS.get("decode_megakernel_scope")
    try:
        with pytest.raises(ValueError, match="decode_megakernel_scope"):
            set_flags({"decode_megakernel_scope": "kernel"})
        assert GLOBAL_FLAGS.get("decode_megakernel_scope") == old
        set_flags({"decode_megakernel_scope": "model"})
        assert GLOBAL_FLAGS.get("decode_megakernel_scope") == "model"
    finally:
        GLOBAL_FLAGS.set("decode_megakernel_scope", old)


def test_scope_flag_feeds_engine_and_generator_defaults(deep_model):
    old = GLOBAL_FLAGS.get("decode_megakernel_scope")
    try:
        set_flags({"decode_megakernel_scope": "model"})
        eng = LLMEngine(deep_model, max_len=32, page_size=4)
        assert eng.megakernel_scope == "model"
        gen = Generator(deep_model, max_len=64)
        assert gen.megakernel_scope == "model"
        prompt = _prompts(deep_model, [5], seed=25)[0]
        ids = paddle.to_tensor(np.asarray(prompt)[None], dtype="int64")
        out = gen.generate(ids, max_new_tokens=8, burst_tokens=1).numpy()
        set_flags({"decode_megakernel_scope": "layer"})
        ref = Generator(deep_model, max_len=64).generate(
            ids, max_new_tokens=8, burst_tokens=1).numpy()
        assert (out == ref).all()
    finally:
        GLOBAL_FLAGS.set("decode_megakernel_scope", old)


def test_autotune_key_separates_scope_and_stacked_geometry(monkeypatch):
    """Layer-scope and model-scope tunings must never share a cache
    line: the key carries the scan scope AND the stacked depth."""
    import paddle_tpu.kernels.autotune as at
    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    seen = []
    monkeypatch.setattr(at, "autotune_enabled", lambda: True)

    def record(key, requested, candidates, build_fn, traced=False):
        seen.append(key)
        return requested
    monkeypatch.setattr(at, "pick_cached", record)

    fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens, self_kv=True,
                       interpret=True, **kw)
    fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens, self_kv=True,
                       interpret=True, scope="model", num_layers=3, **kw)
    fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens, self_kv=True,
                       interpret=True, scope="model", num_layers=5, **kw)
    assert len(seen) == 3
    assert len(set(seen)) == 3, seen
    assert seen[0][-2:] == ("layer", 1)
    assert seen[1][-2:] == ("model", 3)
    assert seen[2][-2:] == ("model", 5)
    # everything BUT the provenance suffix is the same geometry
    assert seen[0][:-2] == seen[1][:-2] == seen[2][:-2]


def test_megakernel_mode_reports_jnp_after_tripped_fallback(monkeypatch):
    """Satellite honesty fix: when FLAGS_enable_fusion_fallback forced
    the jnp body at run time, megakernel_mode must say ``jnp`` — not
    echo the environment's kernel selection — until the trip is reset."""
    import paddle_tpu.kernels.decode_megakernel as dm
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    reset_megakernel_fallback()
    assert not megakernel_fallback_tripped()
    assert megakernel_mode() == "interpret"

    layer, h, Kp, Vp, tbls, kv_lens, kw = _layer_fixture()
    ref = _reference_layer(layer, h, Kp, Vp, tbls, kv_lens, self_kv=True,
                           k_scales=None, v_scales=None, **kw)

    def boom(*a, **k):
        raise RuntimeError("simulated pallas lowering failure")
    monkeypatch.setattr(dm.pl, "pallas_call", boom)
    # the reroute is opt-in: by default a refused kernel fails loudly
    assert GLOBAL_FLAGS.get("enable_fusion_fallback") is False
    with pytest.raises(RuntimeError, match="simulated pallas"):
        fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens,
                           self_kv=True, interpret=True, **kw)
    assert not megakernel_fallback_tripped()
    GLOBAL_FLAGS.set("enable_fusion_fallback", True)
    try:
        out = fused_decode_layer(layer, h, Kp, Vp, tbls, kv_lens,
                                 self_kv=True, interpret=True, **kw)
        # the fallback still computed the right answer...
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   rtol=1e-5, atol=1e-5)
        # ...and the mode now admits the reroute
        assert megakernel_fallback_tripped()
        assert megakernel_mode() == "jnp"
        # with the fallback flag off, the trip is not a reroute promise
        GLOBAL_FLAGS.set("enable_fusion_fallback", False)
        assert megakernel_mode() == "interpret"
    finally:
        GLOBAL_FLAGS.set("enable_fusion_fallback", False)
        reset_megakernel_fallback()
    assert megakernel_mode() == "interpret"
