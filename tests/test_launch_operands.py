"""The serving launch is one put, one call, one read-back (ISSUE 32).

``spec_decode.StepOperands`` lays the ragged step's small operands out in
ONE int32 control buffer; ``LLMEngine._launch`` writes the rows into views
of it, puts it once, calls the one executable and reads one int32 array
back. Held here:

- the layout: ``views`` on the host and ``unpack`` in the graph are two
  readings of the same slices, every operand equal, floats bit for bit;
- the count: two transfers a dispatch (``metrics.host_transfers``), one
  host array put a step;
- the tokens: what greedy and seeded-sampling requests emitted at the
  parent commit (the golden lists below, recorded there on the CPU);
- the guard: a row whose logits go non-finite is aborted, its neighbour's
  tokens untouched, both out of the one read-back.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (ExaoneMoeConfig, ExaoneMoeForCausalLM,
                               LlamaForCausalLM, llama_tiny_config)
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.kv_cache import NULL_PAGE
from paddle_tpu.serving.spec_decode import StepOperands

EXAONE_SIZES = dict(vocab_size=64, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=5,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=32, sliding_window=8, num_experts=8,
                    num_experts_per_tok=2, initializer_range=0.08,
                    dtype="float32")


def _llama():
    paddle.seed(7)
    return LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=2, hidden_size=64, intermediate_size=128,
        num_attention_heads=2, num_key_value_heads=2, vocab_size=128))


def _engine(kind, **kw):
    if kind == "llama":
        return LLMEngine(_llama(), max_len=64, page_size=4, max_num_seqs=4,
                         **kw)
    if kind == "spec":
        model = _llama()
        return LLMEngine(model, max_len=64, page_size=4, max_num_seqs=4,
                         draft_model=model, spec_tokens=2, **kw)
    assert kind == "exaone"
    paddle.seed(11)
    model = ExaoneMoeForCausalLM(ExaoneMoeConfig(**EXAONE_SIZES)).eval()
    return LLMEngine(model, max_len=128, page_size=4, max_num_seqs=4,
                     chunk_size=16, q_block=4, prefix_caching=False, **kw)


def _serve(eng, vocab):
    """Two greedy requests and two seeded-sampling ones, one batch."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, vocab, (n,)).tolist() for n in (5, 19, 11, 7)]
    rids = [eng.add_request(prompts[0], max_new_tokens=8),
            eng.add_request(prompts[1], max_new_tokens=8),
            eng.add_request(prompts[2], max_new_tokens=8, temperature=0.8,
                            top_k=12, top_p=0.9, seed=1234),
            eng.add_request(prompts[3], max_new_tokens=8, temperature=1.3,
                            top_p=0.7, seed=99)]
    outs = eng.run(max_steps=400)
    assert all(outs[r].status == "finished" for r in rids)
    return [outs[r].token_ids for r in rids]


# what _serve(_engine(kind), VOCAB[kind]) emitted at the parent commit
# (e3e4953), on the CPU at float32 "highest" (equal with one thread and
# with many)
GOLDEN = {
    "llama": [[15, 49, 49, 49, 49, 45, 109, 55],
              [91, 98, 49, 45, 49, 45, 49, 45],
              [127, 12, 46, 46, 117, 5, 42, 61],
              [39, 74, 94, 51, 117, 46, 58, 37]],
    "exaone": [[59, 41, 61, 39, 32, 39, 32, 25],
               [32, 25, 28, 25, 37, 29, 0, 35],
               [52, 23, 48, 11, 14, 5, 38, 27],
               [26, 22, 62, 58, 58, 41, 2, 19]],
    "spec": [[15, 49, 49, 49, 49, 45, 109, 55],
             [91, 98, 49, 45, 49, 45, 49, 45],
             [127, 83, 124, 52, 37, 96, 24, 5],
             [39, 63, 59, 51, 66, 114, 85, 105]],
}
VOCAB = {"llama": 128, "exaone": 64, "spec": 128}


# ---------------------------------------------------------------------------
# (a) the layout: host views and the graph's unpack read the same slices
# ---------------------------------------------------------------------------

def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("adapters", [False, True], ids=["base", "lora"])
@pytest.mark.parametrize("K", [0, 2])
def test_pack_unpack_round_trip(K, adapters, window):
    T, R, PPS = 96, 4, 16
    lay = StepOperands(T, R, PPS, K, window=window, adapters=adapters)
    want = {"tokens": (T,), "positions": (T,), "tbls": (R, PPS),
            "q_starts": (R,), "q_lens": (R,), "kv_lens": (R,),
            "sample_idx": (R, K + 1), "temps": (R,), "top_ks": (R,),
            "top_ps": (R,), "seeds": (R,), "sample_pos": (R,),
            "spec_lens": (R,)}
    if window:
        want["tbls_w"] = (R, PPS)
    if adapters:
        want["slot_ids"] = (T,)
    buf, views = lay.host()
    assert buf.dtype == np.int32 and buf.shape == (lay.size,)
    assert lay.size == sum(int(np.prod(s)) for s in want.values())
    assert {k: v.shape for k, v in views.items()} == want
    # a fresh buffer is all pad rows
    assert (views["tbls"] == NULL_PAGE).all() and (views["q_starts"] == T).all()
    assert (views["top_ps"] == 1.0).all() and not views["q_lens"].any()
    rng = np.random.default_rng(K + 2 * adapters + 4 * window)
    filled = {}
    for name, v in views.items():
        if v.dtype == np.float32:
            # any bits at all: negative zero, denormals, NaNs, infinities
            x = rng.integers(-2**31, 2**31, v.shape).astype(np.int32) \
                .view(np.float32)
            x.reshape(-1)[:3] = [-0.0, 1e-42, np.inf]
        else:
            x = rng.integers(-2**31, 2**31, v.shape).astype(np.int32)
        v[...] = x
        filled[name] = x
    got = jax.jit(lay.unpack)(jnp.asarray(buf))
    assert set(got) == set(views)
    for name, x in filled.items():
        g = np.asarray(got[name])
        assert g.dtype == x.dtype and g.shape == x.shape, name
        np.testing.assert_array_equal(_bits(g), _bits(x), err_msg=name)
    # a second buffer starts blank: nothing of this one is kept
    assert not lay.host()[0][:T].any()
    # and the step's small results, one array home
    out = rng.integers(0, 1000, (R, K + 1)).astype(np.int32)
    fin = np.array([True, False, True, True])
    for extra in (0, 3):          # the routed layers' counts behind n_out
        n_out = rng.integers(1, K + 2, (R + extra,)).astype(np.int32)
        back = np.asarray(jax.jit(lay.pack_results)(out, n_out, fin))
        assert back.dtype == np.int32
        o, n, f = lay.read_results(back)
        np.testing.assert_array_equal(o, out)
        np.testing.assert_array_equal(n, n_out)
        np.testing.assert_array_equal(f, fin)


@pytest.mark.parametrize("kind", ["llama", "spec", "exaone"])
def test_lowering_surface_is_the_launch_signature(kind):
    """``_zero_step_args`` hands the lowering what ``_launch``
    dispatches: one control buffer of the layout's size, all pad rows."""
    eng = _engine(kind)
    lay = eng._operands
    assert ("tbls_w" in lay.fields) == (kind == "exaone")
    assert lay.K == (2 if kind == "spec" else 0)
    args = eng._zero_step_args()
    assert len(args) == 8
    ctl = args[3]
    assert ctl.shape == (lay.size,) and ctl.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(ctl), lay.host()[0])
    text = eng.ragged_step_lowering()
    assert f"tensor<{lay.size}xi32>" in text
    assert "bitcast_convert" in text


# ---------------------------------------------------------------------------
# (b) the count: one put and one read-back a dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["llama", "exaone"])
def test_two_transfers_a_dispatch_one_host_array_a_step(kind, monkeypatch):
    eng = _engine(kind)
    rng = np.random.RandomState(1)
    for n in (23, 6, 40):
        eng.add_request(rng.randint(0, VOCAB[kind], (n,)).tolist(),
                        max_new_tokens=12)
    eng.step()                        # the compile, outside the count
    # the call is handed the host buffer itself and makes the one put;
    # nothing else of a step puts a host array
    handed, put, step_jit = [], [], eng._ragged_jit

    def call(*args):
        handed.append([a for a in jax.tree_util.tree_leaves(args)
                       if isinstance(a, np.ndarray)])
        return step_jit(*args)

    def spy(real):
        def f(x, *a, **kw):
            if isinstance(x, np.ndarray):
                put.append(x)
            return real(x, *a, **kw)
        return f
    monkeypatch.setattr(eng, "_ragged_jit", call)
    monkeypatch.setattr(jax, "device_put", spy(jax.device_put))
    monkeypatch.setattr(jnp, "asarray", spy(jnp.asarray))
    m = eng.metrics
    d0, t0, steps = m.host_dispatches.value, m.host_transfers.value, 0
    while eng.has_unfinished() and steps < 12:
        eng.step()
        steps += 1
    monkeypatch.undo()
    dispatches = m.host_dispatches.value - d0
    assert 8 <= dispatches <= steps
    assert m.host_transfers.value - t0 == 2 * dispatches
    assert not put, [p.shape for p in put]
    assert len(handed) == dispatches
    size = eng._operands.size
    assert all(len(h) == 1 and h[0].shape == (size,)
               and h[0].dtype == np.int32 for h in handed)
    snap = eng.metrics_snapshot()
    assert snap["host_transfers"] == 2 * snap["host_dispatches"]
    assert snap["decode_cache_size"] == 1


def test_a_speculative_round_puts_its_candidates_too():
    eng = _engine("spec")
    eng.add_request([5, 9, 2, 7, 7, 1], max_new_tokens=10)
    eng.run(max_steps=100)
    snap = eng.metrics_snapshot()
    assert snap["spec_rounds"] >= 1
    assert snap["host_transfers"] == \
        2 * snap["host_dispatches"] + snap["spec_rounds"]


# ---------------------------------------------------------------------------
# (c) the tokens are the parent's
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(GOLDEN))
def test_tokens_are_the_parents(kind):
    eng = _engine(kind)
    assert _serve(eng, VOCAB[kind]) == GOLDEN[kind]
    assert eng.decode_cache_size() == 1


# ---------------------------------------------------------------------------
# (d) the non-finite guard reads its flags from the same read-back
# ---------------------------------------------------------------------------

def test_one_nonfinite_row_is_aborted_and_its_neighbour_is_not():
    bad_tok = 77
    # the poisoned row fills 16 packed slots, so its neighbour's pages
    # are patched from token windows of their own (kv_append picks a
    # page's rows out of a 16-aligned window of the step's tokens by a
    # 0/1 matmul, through which a NaN inside the window would spread)
    prompts = [[3, 14, bad_tok, 15, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2],
               [2, 6, 5, 3, 5, 8, 9]]

    def serve(poison):
        eng = _engine("llama")
        if poison:
            # one embedding row: only a request that holds the token
            # sees a NaN
            emb = eng.params["embed"].at[bad_tok].set(jnp.nan)
            eng.params["embed"] = eng._ragged_params["embed"] = emb
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        outs = eng.run(max_steps=100)
        return eng, [outs[r] for r in rids]

    _, healthy = serve(False)
    assert [o.status for o in healthy] == ["finished", "finished"]
    eng, (bad, good) = serve(True)
    assert bad.status == "aborted"
    assert bad.finish_reason == "nonfinite_logits"
    assert bad.token_ids == []
    assert good.status == "finished"
    assert good.token_ids == healthy[1].token_ids
    assert bad_tok not in good.token_ids
    snap = eng.metrics_snapshot()
    assert snap["nonfinite_rows"] == 1
    assert snap["host_transfers"] == 2 * snap["host_dispatches"]
    assert eng.pool.free_pages == eng.pool.capacity
