"""KV-cache generation engine: prefill parity with the training forward,
greedy decode = sliding-window full forward, sampling controls.

Mirrors the reference's decode-kernel tests (masked_multihead_attention
unit tests compare against a full-attention recompute).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import sampler_oracle as oracle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config, Generator
from paddle_tpu.models.generation import (_masked_logits, if_any_samples,
                                          request_keys, sample_rows,
                                          sampling_probs)


def _model():
    paddle.seed(11)
    cfg = llama_tiny_config(num_key_value_heads=2)  # exercise GQA
    return LlamaForCausalLM(cfg), cfg


@pytest.mark.slow
def test_prefill_matches_training_forward():
    model, cfg = _model()
    ids_np = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 12))
    gen = Generator(model, max_len=64)
    logits, _ = gen._prefill(gen.params, ids_np)
    full = model(paddle.to_tensor(ids_np, dtype="int64")).numpy()
    np.testing.assert_allclose(np.asarray(logits), full[:, -1], rtol=2e-2,
                               atol=2e-3)


@pytest.mark.slow
def test_greedy_decode_matches_full_forward():
    model, cfg = _model()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (1, 6))
    gen = Generator(model, max_len=64)
    out = gen.generate(paddle.to_tensor(ids, dtype="int64"),
                       max_new_tokens=5, temperature=0.0).numpy()
    assert out.shape == (1, 11)

    # reference: recompute argmax with the full training forward each step
    cur = ids.copy()
    for _ in range(5):
        logits = model(paddle.to_tensor(cur, dtype="int64")).numpy()
        nxt = logits[:, -1].argmax(-1)
        cur = np.concatenate([cur, nxt[:, None]], 1)
    np.testing.assert_array_equal(out, cur)


@pytest.mark.slow
def test_sampling_controls():
    model, cfg = _model()
    ids = paddle.to_tensor(np.array([[1, 2, 3]]), dtype="int64")
    gen = Generator(model, max_len=32)
    a = gen.generate(ids, max_new_tokens=4, temperature=1.0, top_k=5,
                     seed=0).numpy()
    b = gen.generate(ids, max_new_tokens=4, temperature=1.0, top_k=5,
                     seed=1).numpy()
    assert a.shape == b.shape == (1, 7)
    # top_p path executes
    c = gen.generate(ids, max_new_tokens=3, temperature=0.8, top_p=0.9).numpy()
    assert c.shape == (1, 6)
    with pytest.raises(ValueError):
        gen.generate(ids, max_new_tokens=100)  # exceeds max_len


@pytest.mark.slow
def test_eos_padding():
    model, cfg = _model()
    gen = Generator(model, max_len=32)
    ids = paddle.to_tensor(np.array([[1, 2], [3, 4]]), dtype="int64")
    # pick the model's own greedy first tokens as "eos" for row 0 so it
    # finishes immediately; row 1 keeps generating
    first = gen.generate(ids, max_new_tokens=1, temperature=0.0).numpy()
    eos = int(first[0, -1])
    out = gen.generate(ids, max_new_tokens=6, temperature=0.0,
                       eos_token_id=eos).numpy()
    row0_gen = out[0, 2:]
    after_eos = row0_gen[np.argmax(row0_gen == eos) + 1:]
    assert (after_eos == eos).all()  # finished row padded with eos


# ---------------------------------------------------------------------------
# the per-row samplers, gated on the batch's knobs (ISSUE 37), against the
# ungated form they replaced: every row bit-equal, whatever it rides with
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("name", list(oracle.KNOBS))
def test_gated_samplers_are_bit_equal_to_the_ungated(name, jit):
    temps, ks, ps = oracle.knobs(name)
    logits = oracle.logits_for(name, (6,), seed=3)
    keys = request_keys(jax.random.key(5), jnp.arange(6, dtype=jnp.int32),
                        jnp.arange(6, dtype=jnp.int32) + 2, 2)
    wrap = jax.jit if jit else (lambda f: f)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    for new, old, args in [
            (_masked_logits, oracle.masked_logits, (logits, safe_t, ks, ps)),
            (sampling_probs, oracle.sampling_probs, (logits, temps, ks, ps)),
            (sample_rows, oracle.sample_rows, (logits, keys, temps, ks, ps))]:
        got, want = np.asarray(wrap(new)(*args)), np.asarray(wrap(old)(*args))
        assert got.dtype == want.dtype and np.array_equal(got, want), \
            f"{new.__name__} differs from the ungated form under {name}"
    # a greedy row reads its argmax beside whatever samples
    toks = np.asarray(sample_rows(logits, keys, temps, ks, ps))
    greedy = np.asarray(temps) <= 0
    assert np.array_equal(toks[greedy],
                          np.asarray(jnp.argmax(logits, -1))[greedy])


def test_a_rows_sample_does_not_depend_on_its_batch():
    """A sampling row alone, beside greedy rows (the long branch for
    all) and beside rows with other masks: the same token and the same
    distribution, bit for bit."""
    temps, ks, ps = oracle.knobs("top_k_and_top_p")
    logits = oracle.logits_for("top_k_and_top_p", (6,), seed=4)
    keys = request_keys(jax.random.key(1), jnp.arange(6, dtype=jnp.int32),
                        jnp.zeros((6,), jnp.int32), 2)
    toks = np.asarray(sample_rows(logits, keys, temps, ks, ps))
    probs = np.asarray(sampling_probs(logits, temps, ks, ps))
    for i in range(6):
        one = slice(i, i + 1)
        assert toks[i] == int(sample_rows(logits[one], keys[one], temps[one],
                                          ks[one], ps[one])[0])
        assert np.array_equal(probs[i], np.asarray(sampling_probs(
            logits[one], temps[one], ks[one], ps[one]))[0])


@pytest.mark.parametrize("temps,want", [
    ([0.0, 0.0, 0.0], "greedy"), ([0.0, 0.7, 0.0], "sampled"),
    ([-1.0, 0.0, 0.0], "greedy")])
def test_the_gate_reads_the_rows_temperatures_alone(temps, want):
    got = if_any_samples(jnp.asarray(temps, jnp.float32),
                         lambda: jnp.int32(1), lambda: jnp.int32(0))
    assert ("sampled" if int(got) else "greedy") == want


def test_the_gates_stay_conds_under_a_vmap_over_positions():
    """The verifier maps the sampler over its K + 1 positions with the
    knobs closed over: the predicates are then unbatched and the three
    gates stay ``cond``s. With the knobs mapped too, they would become
    selects that run both sides; the control shows this test sees that."""
    temps, ks, ps = oracle.knobs("top_k_and_top_p")
    logits = oracle.logits_for("top_k_and_top_p", (6, 3), seed=5)

    def conds(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count(" cond[")

    assert conds(jax.vmap(lambda lg: sampling_probs(lg, temps, ks, ps),
                          in_axes=1), logits) == 3
    assert conds(jax.vmap(lambda lg, t: sampling_probs(lg, t, ks, ps),
                          in_axes=(1, 1)),
                 logits, jnp.tile(temps[:, None], (1, 3))) < 3
