"""Speculative decoding gates (serving/spec_decode.py, ISSUE 9).

The acceptance bars, asserted not logged:
- greedy parity: an LLMEngine with a draft model produces token-identical
  output to spec-off and to sequential Generator.generate — including
  under chunked prefill, preemption, and prefix forks — and the serving
  trace-count gate stays at ONE ragged executable;
- determinism: a sampled request's tokens are bit-identical for a fixed
  (request_seed, prompt) across different co-scheduled batch
  compositions (per-request fold_in streams), spec-on and spec-off, and
  identical between the per-token and burst paths;
- distribution equivalence: the rejection sampler's induced first-token
  distribution equals the target-only sampling distribution EXACTLY
  (the algebraic identity on a small vocab) and empirically through the
  real jitted sampler;
- KV rollback: rejected tails shrink the committed length without
  freeing pages; pool invariants hold throughout and drain clean;
- the models/generation.py top_k >= vocab clamp regression.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import sampler_oracle as oracle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config, Generator
from paddle_tpu.models.generation import (_sample, if_any_samples,
                                          request_keys, sample_rows,
                                          sampling_probs)
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.spec_decode import StepOperands, speculative_sample


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(7)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=64,
                            intermediate_size=128, num_attention_heads=2,
                            num_key_value_heads=2, vocab_size=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def tiny_draft():
    """A genuinely different (smaller) draft over the same vocab."""
    paddle.seed(23)
    cfg = llama_tiny_config(num_hidden_layers=1, hidden_size=32,
                            intermediate_size=64, num_attention_heads=1,
                            num_key_value_heads=1, vocab_size=128)
    return LlamaForCausalLM(cfg)


def _prompts(model, lengths, seed=0):
    rng = np.random.RandomState(seed)
    v = model.config.vocab_size
    return [rng.randint(0, v, (n,)).tolist() for n in lengths]


def _reference_tokens(model, prompt, n, max_len=64):
    gen = Generator(model, max_len=max_len)
    out = gen.generate(paddle.to_tensor(np.asarray(prompt)[None],
                                        dtype="int64"),
                       max_new_tokens=n, temperature=0.0).numpy()
    return out[0, len(prompt):].tolist()


# ---------------------------------------------------------------------------
# greedy token-identity: spec-on == spec-off == sequential Generator
# ---------------------------------------------------------------------------

def test_spec_greedy_token_identity_mixed_batch(tiny_model, tiny_draft):
    prompts = _prompts(tiny_model, [3, 5, 7, 11])
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4,
                    draft_model=tiny_draft, spec_tokens=3)
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    outs = eng.run(max_steps=300)
    for rid, p in zip(rids, prompts):
        assert outs[rid].status == "finished"
        assert outs[rid].token_ids == _reference_tokens(tiny_model, p, 6), \
            f"{rid} diverged under speculative decoding"
    snap = eng.metrics_snapshot()
    # a random unrelated draft earns ~zero acceptance — the point of the
    # gate is that rejection NEVER changes the greedy output
    assert snap["spec_rounds"] >= 1
    assert snap["spec_drafted_tokens"] >= 1
    # spec rounds rode the ONE ragged executable
    assert snap["decode_cache_size"] == 1
    assert snap["draft_decode_compiles"] == 1
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.capacity
    assert eng._draft.pool.free_pages == eng._draft.pool.capacity


def test_spec_self_draft_accepts_and_beats_one_step_per_token(tiny_model):
    """The int4-quantized SELF-draft (the production int4 path) accepts
    most greedy candidates: target launches per committed token < 1."""
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=2,
                    draft_model=tiny_model, spec_tokens=4)
    rid = eng.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=12)
    outs = eng.run(max_steps=100)
    assert outs[rid].token_ids == _reference_tokens(
        tiny_model, [1, 2, 3, 1, 2, 3, 1, 2], 12)
    snap = eng.metrics_snapshot()
    assert snap["spec_accept_rate"] > 0.0
    assert snap["spec_accepted_tokens"] >= 1
    assert snap["target_steps_per_token"] < 1.0, (
        "speculation must commit more than one token per target launch")
    # the control: without a draft the same request costs exactly one
    # target launch a token
    plain = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=2)
    plain.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=12)
    plain.run(max_steps=100)
    assert plain.metrics_snapshot()["target_steps_per_token"] == 1.0


def test_spec_propose_burst_one_launch_per_round(tiny_model):
    """ROADMAP item 4 leftover: the draft's k proposal steps fold into
    ONE jitted lax.scan burst — a spec round costs one propose launch
    (plus its catch-up sync launches), not k, and the burst compiles
    exactly once."""
    k = 4
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=2,
                    draft_model=tiny_model, spec_tokens=k)
    rid = eng.add_request([1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=12)
    outs = eng.run(max_steps=100)
    assert outs[rid].status == "finished"
    snap = eng.metrics_snapshot()
    rounds = snap["spec_rounds"]
    assert rounds >= 2
    # per round: <= 1 sync chunk launch (the accepted tokens fit one
    # chunk on this workload) + exactly 1 proposal burst. The host-loop
    # path paid 1 + k launches per round.
    assert snap["draft_launches"] <= 2 * rounds + 2, (
        f"{snap['draft_launches']} draft launches over {rounds} rounds: "
        f"the k-step proposal loop is dispatching per step again")
    assert snap["draft_launches"] < rounds * (1 + k)
    assert snap["draft_propose_compiles"] == 1
    assert snap["draft_decode_compiles"] == 1


def test_spec_greedy_identity_under_chunked_prefill(tiny_model, tiny_draft):
    """A long prompt chunks in through ordinary ragged rounds (spec
    rounds require every row caught-up), then speculation takes over —
    output still token-identical."""
    long_p = _prompts(tiny_model, [24], seed=22)[0]
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4,
                    chunk_size=4, draft_model=tiny_model, spec_tokens=3)
    rid = eng.add_request(long_p, max_new_tokens=8)
    outs = eng.run(max_steps=300)
    assert outs[rid].token_ids == _reference_tokens(tiny_model, long_p, 8)
    snap = eng.metrics_snapshot()
    assert snap["prefill_chunks"] >= 3, "the prompt must have chunked"
    assert snap["spec_rounds"] >= 1, "speculation must have engaged"
    assert snap["decode_cache_size"] == 1


def test_spec_greedy_identity_under_preemption_and_prefix_forks(
        tiny_model):
    """The PR 6/7 stress composition, speculative edition: a starved
    pool forces preemption while prefix forks share pages — every
    sequence still reproduces the sequential greedy tokens exactly."""
    prefix = _prompts(tiny_model, [12], seed=34)[0]
    tails = _prompts(tiny_model, [2, 3], seed=35)
    prompts = [prefix] + [prefix + t for t in tails]
    eng = LLMEngine(tiny_model, max_len=32, page_size=4, num_pages=9,
                    max_num_seqs=3, chunk_size=16, high_watermark=1.0,
                    draft_model=tiny_model, spec_tokens=2)
    donor = eng.add_request(prompts[0], max_new_tokens=8)
    eng.step()
    rids = [donor] + [eng.add_request(p, max_new_tokens=8)
                      for p in prompts[1:]]
    outs = eng.run(max_steps=600)
    snap = eng.metrics_snapshot()
    assert snap["prefix_cache_hits"] >= 1, "forks must have happened"
    assert snap["preemptions"] >= 1, "the starved pool must preempt"
    assert snap["spec_rounds"] >= 1
    for rid, p in zip(rids, prompts):
        assert outs[rid].status == "finished"
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, p, 8, max_len=64), \
            f"{rid} diverged under preemption + prefix forks + spec"
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.capacity
    assert eng._draft.pool.free_pages == eng._draft.pool.capacity


def test_spec_eos_mid_chain_finalizes_and_discards_tail(tiny_model):
    """An eos committed mid-verification finalizes the request at that
    token; the chain's remaining accepted tokens are discarded — same
    tokens as the spec-off engine with the same eos."""
    prompt = _prompts(tiny_model, [5], seed=3)[0]
    ref = _reference_tokens(tiny_model, prompt, 6)
    eos = ref[2]
    eng = LLMEngine(tiny_model, max_len=64, page_size=4,
                    draft_model=tiny_model, spec_tokens=4)
    rid = eng.add_request(prompt, max_new_tokens=6, eos_token_id=eos)
    outs = eng.run(max_steps=100)
    assert outs[rid].finish_reason == "eos"
    assert outs[rid].token_ids == ref[:3]
    assert eng.pool.free_pages == eng.pool.capacity


def test_spec_int8_kv_pool_runs_and_drains(tiny_model):
    """Speculation over an int8 paged KV pool: the segmented append
    covers k+1-token verification chunks and rollback leaves the pool
    consistent. (Token identity is NOT asserted here: a rejected
    candidate's append can grow a page's running-amax scale, which is
    a documented int8 x speculation numerics interaction.)"""
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=2,
                    kv_cache_dtype="int8", draft_model=tiny_model,
                    spec_tokens=3)
    prompts = _prompts(tiny_model, [4, 6], seed=9)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run(max_steps=200)
    v = tiny_model.config.vocab_size
    for rid in rids:
        assert outs[rid].status == "finished"
        assert len(outs[rid].token_ids) == 8
        assert all(0 <= t < v for t in outs[rid].token_ids)
    assert eng.metrics_snapshot()["spec_rounds"] >= 1
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.capacity


# ---------------------------------------------------------------------------
# determinism: per-request streams beat batch composition
# ---------------------------------------------------------------------------

def _sampled_probe_tokens(model, draft, co_scheduled, *, spec_tokens=0,
                          burst_tokens=1):
    eng = LLMEngine(model, max_len=64, page_size=4, max_num_seqs=4,
                    seed=5, burst_tokens=burst_tokens,
                    draft_model=draft if spec_tokens else None,
                    spec_tokens=spec_tokens)
    eng.add_request([9, 8, 7], max_new_tokens=8, temperature=0.8,
                    top_k=20, top_p=0.95, seed=1234, request_id="probe")
    for i in range(co_scheduled):
        eng.add_request([i + 1, i + 2, i + 3, i + 4], max_new_tokens=6,
                        temperature=0.5, seed=i)
    return eng.run(max_steps=400)["probe"].token_ids


def test_sampled_request_bit_identical_across_batch_compositions(
        tiny_model, tiny_draft):
    alone = _sampled_probe_tokens(tiny_model, tiny_draft, 0)
    with_2 = _sampled_probe_tokens(tiny_model, tiny_draft, 2)
    with_3 = _sampled_probe_tokens(tiny_model, tiny_draft, 3)
    assert alone == with_2 == with_3, \
        "co-scheduling changed a sampled request's tokens"
    s_alone = _sampled_probe_tokens(tiny_model, tiny_draft, 0,
                                    spec_tokens=3)
    s_with = _sampled_probe_tokens(tiny_model, tiny_draft, 3,
                                   spec_tokens=3)
    assert s_alone == s_with, \
        "co-scheduling changed a SPECULATIVE sampled request's tokens"


def test_sampled_tokens_identical_per_token_vs_burst(tiny_model):
    """The burst loop draws from the same (seed, position) streams as
    the per-token path — sampled outputs are identical, not just
    greedy ones."""
    per_token = _sampled_probe_tokens(tiny_model, None, 1)
    burst = _sampled_probe_tokens(tiny_model, None, 1, burst_tokens=4)
    assert per_token == burst


def test_request_seed_defaults_are_stable(tiny_model):
    """seed=None derives from the request_id: two engines, same ids,
    same sampled tokens; an explicit different seed diverges."""
    def run(seed):
        eng = LLMEngine(tiny_model, max_len=32, page_size=4)
        eng.add_request([4, 5, 6], max_new_tokens=6, temperature=0.9,
                        seed=seed, request_id="r")
        return eng.run(max_steps=100)["r"].token_ids

    assert run(None) == run(None)
    assert run(7) == run(7)
    assert run(7) != run(8) or run(7) != run(9)  # streams actually differ


# ---------------------------------------------------------------------------
# the rejection sampler: exact distribution equivalence on a small vocab
# ---------------------------------------------------------------------------

def test_rejection_sampler_algebraic_identity_small_vocab():
    """The identity the sampler implements: for ANY draft distribution
    q and target distribution p, q(t)*min(1, p(t)/q(t)) +
    P(reject)*residual(t) == p(t) exactly. Computed with the REPO's own
    probability transforms (sampling_probs) at several knob settings."""
    rng = np.random.default_rng(0)
    V = 7
    for trial in range(20):
        tl = jnp.asarray(rng.standard_normal((1, V)), jnp.float32)
        dl = jnp.asarray(rng.standard_normal((1, V)), jnp.float32)
        temps = jnp.asarray([[0.7], [1.3], [1.0]][trial % 3][:1],
                            jnp.float32)
        ks = jnp.asarray([0 if trial % 2 else 4], jnp.int32)
        ps = jnp.asarray([1.0 if trial % 3 else 0.9], jnp.float32)
        p = np.asarray(sampling_probs(tl, temps, ks, ps))[0]
        q = np.asarray(sampling_probs(dl, temps, ks, ps))[0]
        accept = q * np.minimum(1.0, p / np.maximum(q, 1e-30))
        res = np.maximum(p - q, 0.0)
        res_mass = res.sum()
        reject_p = 1.0 - accept.sum()
        induced = accept + (reject_p * res / res_mass
                            if res_mass > 0 else 0.0)
        np.testing.assert_allclose(induced, p, rtol=1e-5, atol=1e-6), \
            f"trial {trial}"


def test_rejection_sampler_empirical_equivalence_and_reproducibility():
    """Drive the REAL jitted sampler: over many per-request streams, the
    empirical first-token distribution of speculative sampling matches
    target-only sampling — and the whole draw set reproduces bit for
    bit per seed."""
    rng = np.random.default_rng(1)
    V, K, N = 5, 2, 4000
    tlog = jnp.asarray(np.tile(rng.standard_normal((1, 1, V)),
                               (N, K + 1, 1)), jnp.float32)
    temps = jnp.ones((N,), jnp.float32)
    ks = jnp.zeros((N,), jnp.int32)
    ps = jnp.ones((N,), jnp.float32)
    base = jax.random.key(0)
    seeds = jnp.arange(N, dtype=jnp.int32)     # one stream per "request"
    pos = jnp.zeros((N,), jnp.int32)
    p = np.asarray(sampling_probs(tlog[:, 0], temps, ks, ps))[0]

    # draft distribution deliberately different from the target
    dlog = jnp.asarray(np.tile(rng.standard_normal((1, 1, V)),
                               (N, K, 1)), jnp.float32)
    q = np.asarray(sampling_probs(dlog[:, 0], temps, ks, ps))[0]
    dprobs = jnp.asarray(np.tile(q[None, None], (N, K, 1)), jnp.float32)
    # candidates drawn from q through the draft stream tag
    from paddle_tpu.serving.spec_decode import DRAFT_TAG
    dkeys = request_keys(base, seeds, pos, DRAFT_TAG)
    d0 = jax.vmap(jax.random.categorical)(dkeys, jnp.log(dprobs[:, 0]))
    dtok = jnp.stack([d0, d0], 1).astype(jnp.int32)
    spec_lens = jnp.ones((N,), jnp.int32)      # verify ONE candidate

    sampler = jax.jit(speculative_sample)
    out, n_out = sampler(tlog, dtok, dprobs, spec_lens, temps, ks, ps,
                         base, seeds, pos)
    out2, n_out2 = sampler(tlog, dtok, dprobs, spec_lens, temps, ks, ps,
                           base, seeds, pos)
    assert np.array_equal(np.asarray(out), np.asarray(out2)), \
        "the sampler must reproduce bit for bit per seed"
    first = np.asarray(out)[np.arange(N), 0]
    emp = np.bincount(first, minlength=V) / N
    # target-only draws through the same harness (spec_lens = 0)
    out0, _ = sampler(tlog, dtok, dprobs, jnp.zeros((N,), jnp.int32),
                      temps, ks, ps, base, seeds, pos)
    emp0 = np.bincount(np.asarray(out0)[:, 0], minlength=V) / N
    # both empirical distributions estimate p; 4000 draws, tol ~3 sigma
    tol = 3.0 * np.sqrt(np.maximum(p * (1 - p), 1e-4) / N)
    assert np.all(np.abs(emp - p) <= tol), (emp, p, tol)
    assert np.all(np.abs(emp0 - p) <= tol), (emp0, p, tol)


def test_rejection_sampler_greedy_rows_degenerate_to_argmax():
    """Greedy rows (temp=0): candidate == target argmax is accepted,
    anything else is rejected and replaced BY the argmax — positionwise."""
    V, K = 6, 2
    tlog = jnp.asarray(np.eye(3, V, dtype=np.float32))[None] * 5.0
    # target argmax chain: 0, 1, 2
    dtok_good = jnp.asarray([[0, 1]], jnp.int32)
    dtok_bad = jnp.asarray([[0, 3]], jnp.int32)
    dprob_good = jax.nn.one_hot(dtok_good, V, dtype=jnp.float32)
    dprob_bad = jax.nn.one_hot(dtok_bad, V, dtype=jnp.float32)
    z = jnp.zeros((1,), jnp.int32)
    args = (jnp.full((1,), 2, jnp.int32), jnp.zeros((1,), jnp.float32),
            z, jnp.ones((1,), jnp.float32), jax.random.key(0), z, z)
    out, n = speculative_sample(tlog, dtok_good, dprob_good, *args)
    assert int(n[0]) == 3 and np.asarray(out)[0, :3].tolist() == [0, 1, 2]
    out, n = speculative_sample(tlog, dtok_bad, dprob_bad, *args)
    assert int(n[0]) == 2 and np.asarray(out)[0, :2].tolist() == [0, 1]


# ---------------------------------------------------------------------------
# the epilogue gated on the rows' knobs (ISSUE 37) against the ungated
# form it replaced (tests/sampler_oracle.py): every row's tokens bit-equal
# ---------------------------------------------------------------------------

def _verify_operands(name, K, seed=0):
    """Six rows' verify logits, candidates (half of them the target's
    argmax, so that leading runs of every length occur), the draft's
    distributions, and ``spec_lens`` from 0 to K."""
    temps, ks, ps = oracle.knobs(name)
    rng = np.random.default_rng(seed)
    tlog = oracle.logits_for(name, (6, K + 1), seed=seed)
    top = np.asarray(jnp.argmax(tlog, -1))[:, :K]
    dtok = np.where(rng.random((6, K)) < 0.5, top,
                    rng.integers(0, oracle.V, (6, K))).astype(np.int32)
    dprobs = jax.nn.softmax(jnp.asarray(
        rng.standard_normal((6, K, oracle.V)), jnp.float32), -1)
    spec_lens = jnp.asarray(np.arange(6) % (K + 1), jnp.int32)
    seeds = jnp.asarray(rng.integers(0, 2 ** 31 - 1, 6), jnp.int32)
    pos = jnp.asarray(rng.integers(0, 100, 6), jnp.int32)
    return (tlog, jnp.asarray(dtok), dprobs, spec_lens, temps, ks, ps,
            jax.random.key(11), seeds, pos)


@pytest.mark.parametrize("K", [0, 2])
@pytest.mark.parametrize("name", list(oracle.KNOBS))
def test_gated_verifier_commits_the_ungated_tokens(name, K):
    """(a) all-greedy batches take the argmax rule, (b) mixed batches the
    standing sampler: ``out`` and ``n_out`` equal the ungated form's for
    every row, sampled rows included, plain and speculative."""
    args = _verify_operands(name, K, seed=K + 1)
    out, n_out = jax.jit(speculative_sample)(*args)
    want, want_n = jax.jit(oracle.speculative_sample)(*args)
    assert np.array_equal(np.asarray(n_out), np.asarray(want_n))
    assert np.array_equal(np.asarray(out), np.asarray(want))
    if K:       # the case means something: every run length occurred
        assert len(set(np.asarray(n_out).tolist())) > 1


def test_pad_rows_do_not_flip_the_gates():
    """(c) a step's pad rows (``StepOperands``' blank buffer: ``temps``
    0, ``top_ks`` 0, ``top_ps`` 1) ask for nothing: alone, and beside
    live greedy rows, the step takes the argmax branch; one sampling row
    among them takes the other."""
    _, o = StepOperands(32, 8, 4, 0).host()
    assert not (o["temps"] > 0).any() and not (o["top_ks"] > 0).any() \
        and not (o["top_ps"] < 1.0).any()

    def branch(temps):
        return int(if_any_samples(jnp.asarray(temps), lambda: jnp.int32(1),
                                  lambda: jnp.int32(0)))
    assert branch(o["temps"]) == 0
    o["q_lens"][:3] = 1                     # three live greedy rows
    assert branch(o["temps"]) == 0
    o["temps"][1] = 0.8
    assert branch(o["temps"]) == 1


def test_greedy_request_is_the_same_beside_a_sampling_one(tiny_model):
    """(d) end to end: a greedy request's tokens alone (every step the
    argmax branch) and co-scheduled with a sampling request (every
    shared step the sampler) are the same tokens, Generator's."""
    prompt = [3, 1, 4, 1, 5, 9]

    def run(with_sampler):
        eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=4,
                        seed=5)
        eng.add_request(prompt, max_new_tokens=10, request_id="greedy")
        if with_sampler:
            eng.add_request([2, 7, 1, 8], max_new_tokens=12, temperature=0.8,
                            top_k=50, top_p=0.9, seed=99,
                            request_id="sampler")
        return eng.run(max_steps=200)["greedy"].token_ids

    alone = run(False)
    assert alone == run(True) == _reference_tokens(tiny_model, prompt, 10)


# ---------------------------------------------------------------------------
# engine/scheduler plumbing + validation
# ---------------------------------------------------------------------------

def test_spec_rollback_keeps_pages_and_metrics_count(tiny_model,
                                                     tiny_draft):
    """A rejecting round rolls the committed KV length back without
    freeing pages; the counters record drafted/accepted/rollbacks."""
    eng = LLMEngine(tiny_model, max_len=64, page_size=4, max_num_seqs=2,
                    draft_model=tiny_draft, spec_tokens=3)
    rid = eng.add_request(_prompts(tiny_model, [6], seed=1)[0],
                          max_new_tokens=10)
    eng.step()                                    # prefill round
    seq = eng._seqs[rid]
    pages_before = len(eng.pool.block_table(rid))
    eng.step()                                    # first spec round
    snap = eng.metrics_snapshot()
    assert snap["spec_rounds"] == 1
    assert snap["spec_drafted_tokens"] == 3
    # the pool's committed length matches the engine's view exactly and
    # the claimed pages were NOT given back on rollback
    assert eng.pool.seq_len(rid) == seq.cached_len
    assert len(eng.pool.block_table(rid)) >= pages_before
    eng.pool.check_invariants()
    if snap["spec_accepted_tokens"] < snap["spec_drafted_tokens"]:
        assert snap["spec_rollbacks"] >= 1
    eng.run(max_steps=100)


def test_wide_seed_masked_not_fatal(tiny_model):
    """Regression: a per-request seed outside int32 range must not blow
    up the serving loop at operand packing — it is masked into range
    (same mask as the request_id-derived default)."""
    eng = LLMEngine(tiny_model, max_len=32, page_size=4)
    rid = eng.add_request([1, 2, 3], max_new_tokens=4, temperature=0.9,
                          seed=2 ** 31)       # > int32 max
    outs = eng.run(max_steps=100)
    assert outs[rid].status == "finished"
    assert len(outs[rid].token_ids) == 4

    def run(seed):
        e = LLMEngine(tiny_model, max_len=32, page_size=4)
        e.add_request([1, 2, 3], max_new_tokens=4, temperature=0.9,
                      seed=seed, request_id="r")
        return e.run(max_steps=100)["r"].token_ids

    assert run(5) == run(5 + 2 ** 31)         # masking is the contract


def test_draft_pool_exhaustion_demotes_round_not_kills_loop(tiny_model):
    """An operator-under-sized DRAFT pool must never kill the serving
    loop: the spec round demotes to an ordinary decode round (target
    claims rolled back, draft state dropped) and greedy output stays
    token-identical."""
    prompts = _prompts(tiny_model, [6, 8], seed=11)
    # 3 usable draft pages of 4 tokens cannot hold two sequences' full
    # contexts — sync/propose must hit PoolExhausted
    eng = LLMEngine(tiny_model, max_len=32, page_size=4, max_num_seqs=2,
                    draft_model=tiny_model, spec_tokens=3,
                    draft_num_pages=4)
    rids = [eng.add_request(p, max_new_tokens=8) for p in prompts]
    outs = eng.run(max_steps=300)
    snap = eng.metrics_snapshot()
    assert snap["spec_draft_fallbacks"] >= 1, \
        "the starved draft pool must have demoted at least one round"
    for rid, p in zip(rids, prompts):
        assert outs[rid].status == "finished"
        assert outs[rid].token_ids == \
            _reference_tokens(tiny_model, p, 8), \
            f"{rid} diverged across draft-pool fallback rounds"
    eng.pool.check_invariants()
    assert eng.pool.free_pages == eng.pool.capacity


def test_spec_burst_mutually_exclusive(tiny_model, tiny_draft):
    with pytest.raises(ValueError, match="mutually exclusive"):
        LLMEngine(tiny_model, max_len=32, page_size=4,
                  draft_model=tiny_draft, spec_tokens=2, burst_tokens=4)


def test_spec_vocab_mismatch_rejected(tiny_model):
    paddle.seed(3)
    other = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=1, hidden_size=32, intermediate_size=64,
        num_attention_heads=1, num_key_value_heads=1, vocab_size=64))
    with pytest.raises(ValueError, match="vocab"):
        LLMEngine(tiny_model, max_len=32, page_size=4, draft_model=other,
                  spec_tokens=2)


def test_spec_flag_and_defaults(tiny_model, tiny_draft):
    from paddle_tpu.core.flags import GLOBAL_FLAGS
    # no draft model: spec stays off regardless of the flag
    eng = LLMEngine(tiny_model, max_len=32, page_size=4)
    assert eng.spec_tokens == 0 and eng._draft is None
    # draft model with nothing else: a sane default k
    eng = LLMEngine(tiny_model, max_len=32, page_size=4,
                    draft_model=tiny_draft)
    assert eng.spec_tokens == 4
    # the flag steers the default
    GLOBAL_FLAGS.set("spec_decode_tokens", 2)
    try:
        eng = LLMEngine(tiny_model, max_len=32, page_size=4,
                        draft_model=tiny_draft)
        assert eng.spec_tokens == 2
    finally:
        GLOBAL_FLAGS.set("spec_decode_tokens", 0)
    with pytest.raises(ValueError):
        GLOBAL_FLAGS.set("spec_decode_tokens", -1)
    # an explicit too-small step budget is a loud error, not a silent
    # shrink (shrinking spec_len would break stream determinism)
    with pytest.raises(ValueError, match="step_token_budget"):
        LLMEngine(tiny_model, max_len=32, page_size=4, max_num_seqs=4,
                  q_block=4, step_token_budget=16,
                  draft_model=tiny_draft, spec_tokens=4)


# ---------------------------------------------------------------------------
# models/generation.py satellite: top_k clamp + per-row masking
# ---------------------------------------------------------------------------

def test_sample_top_k_clamps_to_vocab():
    """Regression: top_k >= vocab used to index sorted[:, -top_k] out of
    range at trace time; it must behave as top_k-off instead."""
    logits = jnp.asarray(np.random.default_rng(0)
                         .standard_normal((3, 8)), jnp.float32)
    key = jax.random.key(0)
    over = _sample(logits, key, 1.0, 100, None)      # top_k >> V
    off = _sample(logits, key, 1.0, None, None)
    assert np.array_equal(np.asarray(over), np.asarray(off))
    exact = _sample(logits, key, 1.0, 8, None)       # top_k == V
    assert np.array_equal(np.asarray(exact), np.asarray(off))
    # and under jit (where the old code died at trace time)
    jitted = jax.jit(_sample, static_argnums=(2, 3, 4))
    assert np.array_equal(np.asarray(jitted(logits, key, 1.0, 100, None)),
                          np.asarray(off))


def test_sample_rows_per_row_knobs_and_streams():
    """Per-row knobs really are per-row: a greedy row takes argmax, a
    top_k=1 row takes argmax too (via masking), and two rows with the
    same seed/position draw identically regardless of neighbors."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((3, 16)), jnp.float32)
    temps = jnp.asarray([0.0, 1.0, 0.9], jnp.float32)
    ks = jnp.asarray([0, 1, 0], jnp.int32)
    ps = jnp.asarray([1.0, 1.0, 0.9], jnp.float32)
    base = jax.random.key(0)
    keys = request_keys(base, jnp.asarray([1, 2, 3]),
                        jnp.asarray([0, 0, 0]), 2)
    toks = np.asarray(sample_rows(logits, keys, temps, ks, ps))
    assert toks[0] == int(jnp.argmax(logits[0]))
    assert toks[1] == int(jnp.argmax(logits[1]))     # top_k=1 == argmax
    # same (seed, position, tag) => same draw, whatever the batch looks
    # like around it
    keys_b = request_keys(base, jnp.asarray([3]), jnp.asarray([0]), 2)
    solo = np.asarray(sample_rows(logits[2:3], keys_b, temps[2:3],
                                  ks[2:3], ps[2:3]))
    assert toks[2] == solo[0]


def test_sampling_probs_greedy_one_hot_and_mass():
    logits = jnp.asarray(np.random.default_rng(3)
                         .standard_normal((2, 12)), jnp.float32)
    p = np.asarray(sampling_probs(
        logits, jnp.asarray([0.0, 0.8]), jnp.asarray([0, 5]),
        jnp.asarray([1.0, 0.9])))
    assert p[0].max() == 1.0 and p[0].sum() == 1.0       # one-hot argmax
    assert p[0].argmax() == int(jnp.argmax(logits[0]))
    np.testing.assert_allclose(p[1].sum(), 1.0, rtol=1e-6)
    assert (p[1] > 1e-7).sum() <= 5                      # top-5 masked
