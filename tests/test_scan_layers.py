"""Scan-over-layers training path (nn/scan_stack.py) + satellites.

Gates, mirroring the optimizer dispatch-gate style:
- parity: scanned vs unrolled llama-tiny logits are BITWISE equal under
  jit (the TrainStep regime — both paths compile to the same per-layer
  kernels); gradients match to float-reassociation tolerance (XLA fuses
  the scan backward's reductions differently than straight-line code);
- trace-size gate: the scanned forward's jaxpr equation count is
  INDEPENDENT of num_hidden_layers while the unrolled path grows
  linearly — the O(1)-in-depth claim, hard-checked;
- grad accumulation: TrainStep(accumulate_steps=K) equals one K×-batch
  step (≤1e-6 f32 on a linear-update optimizer) at ONE host dispatch
  per optimizer step;
- state_dict: per-layer names round-trip through the stacked storage in
  both directions;
- flag-off parity: FLAGS_scan_layers=False + FLAGS_remat_policy=none is
  the pre-scan model, bit for bit.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import autograd as _ag
from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.nn.scan_stack import LayerStack, effective_remat_policy


@pytest.fixture(autouse=True)
def _reset_flags():
    yield
    GLOBAL_FLAGS.set("scan_layers", False)
    GLOBAL_FLAGS.set("remat_policy", "none")


def _build(scan, **cfg_kw):
    GLOBAL_FLAGS.set("scan_layers", scan)
    try:
        return LlamaForCausalLM(llama_tiny_config(**cfg_kw))
    finally:
        GLOBAL_FLAGS.set("scan_layers", False)


def _ids(batch=2, seq=16, vocab=512, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (batch, seq))


def _functional_logits(model):
    """Functionalize the model forward for jit/make_jaxpr."""
    params = dict(model.named_parameters())

    def f(arrs, ids_arr):
        saved = {k: p._data for k, p in params.items()}
        try:
            for k, p in params.items():
                p._data = arrs[k]
            with _ag.no_grad():
                return model(Tensor(ids_arr))._data
        finally:
            for k, p in params.items():
                p._data = saved[k]

    return f, {k: p._data for k, p in params.items()}


def _functional_loss(model):
    params = dict(model.named_parameters())

    def f(arrs, ids_arr):
        saved = {k: p._data for k, p in params.items()}
        try:
            for k, p in params.items():
                p._data = arrs[k]
            with _ag.no_grad():
                return model(Tensor(ids_arr), labels=Tensor(ids_arr))[1]._data
        finally:
            for k, p in params.items():
                p._data = saved[k]

    return f, {k: p._data for k, p in params.items()}


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_scan_logits_bitwise_under_jit():
    m1 = _build(False)
    m2 = _build(True)
    assert isinstance(m2.model.layers, LayerStack)
    missing, unexpected = m2.set_state_dict(m1.state_dict())
    assert not missing and not unexpected
    ids = jnp.asarray(_ids())
    f1, a1 = _functional_logits(m1)
    f2, a2 = _functional_logits(m2)
    o1 = jax.jit(f1)(a1, ids)
    o2 = jax.jit(f2)(a2, ids)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def test_scan_grads_match_unrolled_under_jit():
    m1 = _build(False)
    m2 = _build(True)
    m2.set_state_dict(m1.state_dict())
    ids = jnp.asarray(_ids())
    f1, a1 = _functional_loss(m1)
    f2, a2 = _functional_loss(m2)
    g1 = jax.jit(jax.grad(f1))(a1, ids)
    g2 = jax.jit(jax.grad(f2))(a2, ids)
    # per-layer grads: slice the stacked cotangent
    for i in (0, 1):
        q1 = np.asarray(g1[f"model.layers.{i}.self_attn.q_proj.weight"])
        q2 = np.asarray(
            g2["model.layers.self_attn.q_proj.weight"])[i]
        # XLA reassociates the scan backward's fused reductions — not
        # bitwise, but far inside any training-relevant tolerance
        np.testing.assert_allclose(q1, q2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(g1["model.embed_tokens.weight"]),
        np.asarray(g2["model.embed_tokens.weight"]), rtol=1e-5, atol=1e-6)


def test_scan_eager_tape_grads_land_on_stacked_params():
    """The eager path (no jit): one tape node for the whole scan, grads
    arrive leading-axis-stacked on the stacked Parameters."""
    m1 = _build(False)
    m2 = _build(True)
    m2.set_state_dict(m1.state_dict())
    ids = paddle.to_tensor(_ids(), dtype="int64")
    _, l1 = m1(ids, labels=ids)
    _, l2 = m2(ids, labels=ids)
    np.testing.assert_allclose(float(l1.numpy()), float(l2.numpy()),
                               rtol=1e-6)
    l1.backward()
    l2.backward()
    for name in ("self_attn.q_proj.weight", "mlp.down_proj.weight",
                 "input_layernorm.weight"):
        stacked = m2.model.layers.stacked_parameter(name).grad
        assert stacked is not None
        for i in (0, 1):
            ref = dict(m1.named_parameters())[
                f"model.layers.{i}.{name}"].grad
            np.testing.assert_allclose(
                np.asarray(stacked._data[i]), np.asarray(ref._data),
                rtol=1e-5, atol=1e-6)


def test_flag_off_is_pre_scan_model():
    GLOBAL_FLAGS.set("scan_layers", False)
    GLOBAL_FLAGS.set("remat_policy", "none")
    m = LlamaForCausalLM(llama_tiny_config())
    from paddle_tpu import nn
    assert isinstance(m.model.layers, nn.LayerList)
    assert effective_remat_policy(False) == "none"
    names = set(dict(m.named_parameters()))
    assert "model.layers.0.self_attn.q_proj.weight" in names


# ---------------------------------------------------------------------------
# trace-size gate: O(1) in depth
# ---------------------------------------------------------------------------

def _eqn_count(model):
    f, arrs = _functional_logits(model)
    jaxpr = jax.make_jaxpr(f)(arrs, jnp.zeros((1, 8), jnp.int32))
    return len(jaxpr.eqns)


def test_scanned_jaxpr_size_independent_of_depth():
    shallow = _eqn_count(_build(True, num_hidden_layers=2))
    deep = _eqn_count(_build(True, num_hidden_layers=8))
    assert shallow == deep, (
        f"scanned forward must trace O(1) equations in depth "
        f"(2 layers: {shallow} vs 8 layers: {deep})")
    un_shallow = _eqn_count(_build(False, num_hidden_layers=2))
    un_deep = _eqn_count(_build(False, num_hidden_layers=8))
    per_layer = (un_deep - un_shallow) / 6
    assert per_layer >= 10, (
        "unrolled path stopped growing with depth — the gate's "
        "denominator vanished")
    # and the deep scanned program is smaller than even the shallow unroll
    assert deep < un_shallow


# ---------------------------------------------------------------------------
# state_dict round-trip
# ---------------------------------------------------------------------------

def test_state_dict_roundtrip_per_layer_names():
    m_un = _build(False)
    m_sc = _build(True)
    sd_un = m_un.state_dict()
    sd_sc = m_sc.state_dict()
    assert set(sd_un) == set(sd_sc)
    # unrolled -> scanned -> unrolled survives bitwise
    m_sc.set_state_dict(sd_un)
    m_un2 = _build(False)
    missing, unexpected = m_un2.set_state_dict(m_sc.state_dict())
    assert not missing and not unexpected
    for k, v in m_un.state_dict().items():
        np.testing.assert_array_equal(
            np.asarray(v._data), np.asarray(m_un2.state_dict()[k]._data),
            err_msg=k)


def test_layerstack_rejects_buffers_and_heterogeneity():
    from paddle_tpu import nn

    class WithBuffer(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)
            self.register_buffer("b", paddle.to_tensor(np.zeros(4, np.float32)))

    with pytest.raises(ValueError, match="buffers"):
        LayerStack([WithBuffer(), WithBuffer()])
    with pytest.raises(ValueError, match="identical"):
        LayerStack([nn.Linear(4, 4), nn.Linear(4, 8)])


# ---------------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------------

def _train_pair(opt_cls, K, **opt_kw):
    m1 = _build(False)
    m2 = _build(False)
    m2.set_state_dict(m1.state_dict())
    o1 = opt_cls(parameters=m1.parameters(), **opt_kw)
    o2 = opt_cls(parameters=m2.parameters(), **opt_kw)
    s1 = paddle.jit.TrainStep(m1, lambda x: m1(x, labels=x)[1], o1)
    s2 = paddle.jit.TrainStep(m2, lambda x: m2(x, labels=x)[1], o2,
                              accumulate_steps=K)
    return m1, m2, s1, s2


def test_grad_accumulation_matches_full_batch_sgd():
    m1, m2, s1, s2 = _train_pair(paddle.optimizer.SGD, K=4,
                                 learning_rate=0.1)
    ids = paddle.to_tensor(_ids(batch=8), dtype="int64")
    l1 = float(s1(ids).numpy())
    l2 = float(s2(ids).numpy())
    assert abs(l1 - l2) <= 1e-6
    sd1, sd2 = m1.state_dict(), m2.state_dict()
    for k in sd1:
        np.testing.assert_allclose(np.asarray(sd1[k]._data),
                                   np.asarray(sd2[k]._data),
                                   rtol=0, atol=1e-6, err_msg=k)


def test_grad_accumulation_adamw_tracks_full_batch():
    # Adam's g/sqrt(v) update amplifies float-level grad differences near
    # step 1 (m/sqrt(v) ~ sign(g)); the linear-optimizer test above is
    # the ≤1e-6 gate, this one pins the adaptive path to a sane band.
    m1, m2, s1, s2 = _train_pair(paddle.optimizer.AdamW, K=2,
                                 learning_rate=1e-3)
    ids = paddle.to_tensor(_ids(batch=8), dtype="int64")
    l1 = float(s1(ids).numpy())
    l2 = float(s2(ids).numpy())
    assert abs(l1 - l2) <= 1e-6
    sd1, sd2 = m1.state_dict(), m2.state_dict()
    for k in sd1:
        np.testing.assert_allclose(np.asarray(sd1[k]._data),
                                   np.asarray(sd2[k]._data),
                                   rtol=0, atol=1e-3, err_msg=k)


def test_grad_accumulation_one_dispatch_per_step():
    """PR-1 gate invariant: dispatches per optimizer step do not grow
    with K — the whole K-scan + update is ONE compiled call."""
    from paddle_tpu.io.prefetch import PIPELINE_METRICS
    from paddle_tpu.optimizer import fused
    m = _build(False)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters())
    step = paddle.jit.TrainStep(m, lambda x: m(x, labels=x)[1], opt,
                                accumulate_steps=4)
    ids = paddle.to_tensor(_ids(batch=8), dtype="int64")
    step(ids)  # compile
    PIPELINE_METRICS.reset()
    before = fused.dispatch_count()
    step(ids)
    assert PIPELINE_METRICS.snapshot()["step_dispatches"] == 1
    # steady state launches no extra eager optimizer dispatches either
    assert fused.dispatch_count() == before


def test_grad_accumulation_ragged_tail_falls_back():
    """A drop_last=False tail batch that does not divide by K runs as
    one micro-batch (same mean-grad update) with a warning instead of
    crashing an epoch of training at its last step."""
    m1, m2, s1, s2 = _train_pair(paddle.optimizer.SGD, K=3,
                                 learning_rate=0.1)
    ids = paddle.to_tensor(_ids(batch=8), dtype="int64")  # 8 % 3 != 0
    with pytest.warns(UserWarning, match="without accumulation"):
        l2 = float(s2(ids).numpy())
    l1 = float(s1(ids).numpy())
    assert abs(l1 - l2) <= 1e-6
    sd1, sd2 = m1.state_dict(), m2.state_dict()
    for k in sd1:
        np.testing.assert_allclose(np.asarray(sd1[k]._data),
                                   np.asarray(sd2[k]._data),
                                   rtol=0, atol=1e-6, err_msg=k)


def test_scaler_explicit_unscale_not_applied_twice():
    """unscale_() followed by step() must unscale exactly once (the
    double-division bug would silently shrink every grad by 1/scale²)."""
    params = _scaler_params(8)
    opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=params)
    sc = paddle.amp.GradScaler(init_loss_scaling=4.0)
    sc.unscale_(opt)
    sc.step(opt)            # must NOT re-unscale
    sc.update()
    np.testing.assert_allclose(np.asarray(params[1].grad._data),
                               np.full((4, 4), 0.5, np.float32))
    sc.unscale_(opt)        # fresh step: allowed again after update()
    with pytest.raises(RuntimeError, match="already"):
        sc.unscale_(opt)    # double unscale before update() raises


# ---------------------------------------------------------------------------
# remat policies
# ---------------------------------------------------------------------------

def test_remat_policies_preserve_values():
    """Remat changes WHEN activations are (re)computed, never what they
    are: loss and grads agree across all three policies."""
    m = _build(True)
    ids = paddle.to_tensor(_ids(), dtype="int64")
    results = {}
    for pol in ("none", "dots_saveable", "full"):
        GLOBAL_FLAGS.set("remat_policy", pol)
        for p in m.parameters():
            p.clear_grad()
        _, loss = m(ids, labels=ids)
        loss.backward()
        g = m.model.layers.stacked_parameter(
            "self_attn.q_proj.weight").grad._data
        results[pol] = (float(loss.numpy()), np.asarray(g))
    base_l, base_g = results["none"]
    for pol in ("dots_saveable", "full"):
        l, g = results[pol]
        assert abs(l - base_l) <= 1e-6, pol
        np.testing.assert_allclose(g, base_g, rtol=1e-5, atol=1e-7,
                                   err_msg=pol)


def test_remat_policy_flag_validates():
    with pytest.raises(ValueError, match="remat_policy"):
        GLOBAL_FLAGS.set("remat_policy", "everything")
    assert GLOBAL_FLAGS.get("remat_policy") in (
        "none", "dots_saveable", "full")


def test_config_remat_maps_to_full():
    assert effective_remat_policy(True) == "full"
    GLOBAL_FLAGS.set("remat_policy", "dots_saveable")
    # an explicit flag wins over the legacy spelling
    assert effective_remat_policy(True) == "dots_saveable"


def test_flops_per_token_accounts_remat_recompute():
    m = _build(False)
    base = m.flops_per_token(128, remat_policy="none")
    full = m.flops_per_token(128, remat_policy="full")
    n = sum(p.size for p in m.parameters())
    attn = 12 * m.config.num_hidden_layers * m.config.hidden_size * 128
    assert full - base == 2 * n + attn // 3
    assert m.flops_per_token(128, remat_policy="dots_saveable") == base


def test_config_validates_head_divisibility():
    from paddle_tpu.models import LlamaConfig
    with pytest.raises(ValueError, match="num_attention_heads"):
        LlamaConfig(hidden_size=100, num_attention_heads=3)
    with pytest.raises(ValueError, match="num_key_value_heads"):
        llama_tiny_config(num_attention_heads=4, num_key_value_heads=3)


# ---------------------------------------------------------------------------
# TrainStep compile forensics (profiler satellite)
# ---------------------------------------------------------------------------

def test_trainstep_records_compile_event():
    from paddle_tpu.core import native as nv
    nv.ensure_loaded()
    if not nv.AVAILABLE:
        pytest.skip("native runtime not built")
    from paddle_tpu import profiler
    m = _build(False, num_hidden_layers=1)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, lambda x: m(x, labels=x)[1], opt)
    ids = paddle.to_tensor(_ids(), dtype="int64")
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    step(ids)          # first call: trace + compile -> `train.compile` span
    step(ids)          # steady state: no new span
    prof.stop()
    names = [e[0] for e in prof.events()]
    compiles = [n for n in names if n == "train.compile"]
    assert len(compiles) == 1, compiles
    assert step.last_compile_ms is not None and step.last_compile_ms > 0
    assert step.compile_ms_total >= step.last_compile_ms
    # a remat flag flip re-specializes — visible as another compile span
    GLOBAL_FLAGS.set("remat_policy", "full")
    prof2 = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof2.start()
    step(ids)
    prof2.stop()
    names2 = [e[0] for e in prof2.events()]
    assert "train.compile" in names2


# ---------------------------------------------------------------------------
# AmpScaler fused finiteness (amp satellite)
# ---------------------------------------------------------------------------

def _scaler_params(n=40):
    params = []
    for i in range(n):
        dt = "bfloat16" if i % 4 == 0 else "float32"
        t = paddle.to_tensor(np.zeros((4, 4), np.float32), dtype=dt)
        t.stop_gradient = False
        t.grad = paddle.to_tensor(np.full((4, 4), 2.0, np.float32), dtype=dt)
        params.append(t)
    return params


def test_scaler_unscale_is_one_dispatch_and_lazy():
    from paddle_tpu.optimizer import fused
    params = _scaler_params()
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=params)
    sc = paddle.amp.GradScaler(init_loss_scaling=4.0)
    before = fused.dispatch_count()
    sc.unscale_(opt)
    assert fused.dispatch_count() - before == 1, (
        "unscale+check must be ONE fused dispatch, not O(n_params)")
    # verdict not yet resolved (no host sync from unscale_ itself)
    assert sc._pending_finite is not None
    np.testing.assert_allclose(np.asarray(params[1].grad._data),
                               np.full((4, 4), 0.5, np.float32))
    assert sc._found_inf is False       # reading it resolves
    assert sc._pending_finite is None


def test_scaler_detects_inf_and_skips_step():
    params = _scaler_params(8)
    params[3].grad = paddle.to_tensor(
        np.full((4, 4), np.inf, np.float32))
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=params)
    sc = paddle.amp.GradScaler(init_loss_scaling=2.0)
    before = np.asarray(params[0]._data).copy()
    sc.step(opt)
    sc.update()
    assert sc._found_inf is True
    np.testing.assert_array_equal(np.asarray(params[0]._data), before)
    assert sc.get_scale_ratio() == 1.0  # one bad step halves 2.0 -> 1.0


# ---------------------------------------------------------------------------
# MoE: dense runs scan, routed layers stay unrolled
# ---------------------------------------------------------------------------

def test_moe_dense_runs_scan_with_parity():
    from paddle_tpu.models.llama_moe import (
        LlamaMoeForCausalLM, llama_moe_tiny_config)
    cfg_kw = dict(num_hidden_layers=4, moe_layer_interval=3)
    GLOBAL_FLAGS.set("scan_layers", False)
    m1 = LlamaMoeForCausalLM(llama_moe_tiny_config(**cfg_kw))
    GLOBAL_FLAGS.set("scan_layers", True)
    m2 = LlamaMoeForCausalLM(llama_moe_tiny_config(**cfg_kw))
    GLOBAL_FLAGS.set("scan_layers", False)
    stacks = [l for l in m2.model.layers if isinstance(l, LayerStack)]
    assert len(stacks) == 1 and stacks[0].num_layers == 2  # layers 1..2
    sd1 = m1.state_dict()
    assert set(sd1) == set(m2.state_dict())
    missing, unexpected = m2.set_state_dict(sd1)
    assert not missing and not unexpected
    ids = paddle.to_tensor(_ids(vocab=256), dtype="int64")
    _, l1 = m1(ids, labels=ids)
    _, l2 = m2(ids, labels=ids)
    np.testing.assert_allclose(float(l1.numpy()), float(l2.numpy()),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# serving/generation bridge keeps working on scanned models
# ---------------------------------------------------------------------------

def test_extract_params_unstacks_scanned_model():
    from paddle_tpu.models.generation import extract_params
    m1 = _build(False)
    m2 = _build(True)
    m2.set_state_dict(m1.state_dict())
    p1 = extract_params(m1)
    p2 = extract_params(m2)
    assert len(p1["layers"]) == len(p2["layers"])
    for l1, l2 in zip(p1["layers"], p2["layers"]):
        for k in l1:
            np.testing.assert_array_equal(np.asarray(l1[k]),
                                          np.asarray(l2[k]), err_msg=k)


# ---------------------------------------------------------------------------
# hapi surface
# ---------------------------------------------------------------------------

def test_hapi_prepare_accumulate_steps():
    class _DS(paddle.io.Dataset):
        def __init__(self, n=32):
            rng = np.random.default_rng(0)
            self.x = rng.standard_normal((n, 8)).astype(np.float32)
            self.y = rng.standard_normal((n, 1)).astype(np.float32)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return len(self.x)

    net = paddle.nn.Linear(8, 1)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
                  paddle.nn.MSELoss(), use_jit=True, accumulate_steps=2)
    model.fit(_DS(), batch_size=8, epochs=1, verbose=0)
    assert model._train_step.accumulate_steps == 2
    with pytest.raises(ValueError, match="use_jit"):
        paddle.Model(net).prepare(
            paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=net.parameters()),
            paddle.nn.MSELoss(), accumulate_steps=2)
