"""Fused multi-tensor optimizer parity (optimizer/fused.py).

The fused engine — dtype-bucketed flat updates with fused global-norm
clipping — must be numerically indistinguishable from the per-parameter
loop for every supported optimizer, across L1/L2 decay, the AdamW hooks,
mixed f32/bf16 param sets, and params excluded by stop_gradient / missing
grads. The per-param loop (FLAGS_fused_optimizer=False) is the reference.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.kernels.fused_adamw import BUCKET_ALIGN

F32_TOL = 1e-6
BF16_TOL = 2e-2  # one bf16 ulp near 1.0 is ~8e-3


@pytest.fixture
def fused_flag():
    yield
    GLOBAL_FLAGS.set("fused_optimizer", True)


MIXED_SPECS = ([((4, 8), "float32"), ((16,), "float32"), ((), "float32"),
                ((3, 3, 2), "float32"), ((8, 4), "bfloat16"),
                ((5,), "bfloat16")] * 3)


# a bucket past one chunk of the kernel: the f32 total (139,097) is no
# multiple of BUCKET_ALIGN (131,072), so the bucket rests at two chunks
# with a tail of 123,047 zeros; the two norms and the scalar are what
# breaks the alignment, as in every real model
PAST_A_CHUNK = [((300, 450), "float32"), ((2048,), "float32"),
                ((2048,), "float32"), ((), "float32"),
                ((64, 33), "bfloat16"), ((17,), "bfloat16")]
SPECS = {"mixed": MIXED_SPECS, "past_a_chunk": PAST_A_CHUNK}


def _make_params(specs, seed=0):
    rng = np.random.default_rng(seed)
    params = []
    for i, (shape, dtype) in enumerate(specs):
        t = paddle.to_tensor(
            rng.standard_normal(shape).astype(np.float32), dtype=dtype)
        t.stop_gradient = False
        t.name = f"p{i}"
        t.grad = paddle.to_tensor(
            rng.standard_normal(shape).astype(np.float32), dtype=dtype)
        params.append(t)
    return params


def _run(factory, fused, specs=MIXED_SPECS, steps=3, seed=0):
    GLOBAL_FLAGS.set("fused_optimizer", fused)
    params = _make_params(specs, seed)
    opt = factory(params)
    for _ in range(steps):
        opt.step()
    vals = [np.asarray(p.numpy(), np.float64) for p in params]
    state = opt.state_dict()
    return params, vals, state, opt


def _assert_match(specs, a_vals, b_vals):
    for (shape, dtype), a, b in zip(specs, a_vals, b_vals):
        tol = F32_TOL if dtype == "float32" else BF16_TOL
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol,
                                   err_msg=f"{shape} {dtype}")


CASES = {
    "sgd_l2": lambda ps: paddle.optimizer.SGD(
        learning_rate=0.1, parameters=ps, weight_decay=0.01),
    "sgd_l1": lambda ps: paddle.optimizer.SGD(
        learning_rate=0.1, parameters=ps,
        weight_decay=paddle.regularizer.L1Decay(0.01)),
    "momentum_nesterov_clip": lambda ps: paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, use_nesterov=True, parameters=ps,
        weight_decay=0.01, grad_clip=paddle.nn.ClipGradByGlobalNorm(0.5)),
    "adam_clip": lambda ps: paddle.optimizer.Adam(
        learning_rate=0.01, parameters=ps, weight_decay=0.02,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0)),
    "adamw_hooks": lambda ps: paddle.optimizer.AdamW(
        learning_rate=0.01, parameters=ps, weight_decay=0.05,
        apply_decay_param_fun=lambda n: not n.endswith("1"),
        lr_ratio=lambda p: 0.5 if p.name.endswith("2") else 1.0,
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0, auto_skip_clip=True)),
    "adamw_byvalue": lambda ps: paddle.optimizer.AdamW(
        learning_rate=0.01, parameters=ps,
        grad_clip=paddle.nn.ClipGradByValue(0.3)),
    # the kernel-eligible form (uniform hyperparameters), here through the
    # jnp body; test_engine_uses_pallas_kernel_when_forced runs the kernel
    "adamw_plain": lambda ps: paddle.optimizer.AdamW(
        learning_rate=0.01, parameters=ps, weight_decay=0.05),
    "momentum_plain": lambda ps: paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=ps),
}


def _flat_tail(bucket, flat):
    assert flat.shape == (bucket.length,)
    return np.asarray(flat[bucket.total:].astype("float32"))


@pytest.mark.parametrize("specs", sorted(SPECS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_matches_per_param(case, specs, fused_flag):
    factory, specs = CASES[case], SPECS[specs]
    _, fused_vals, fused_state, fused_opt = _run(factory, True, specs=specs)
    _, ref_vals, ref_state, _ = _run(factory, False, specs=specs)
    _assert_match(specs, fused_vals, ref_vals)
    eng = fused_opt._fused_engine
    assert eng is not None and eng.active
    assert len(eng.buckets) == 2  # one f32, one bf16
    # optimizer state matches through the state_dict view too
    assert set(fused_state) == set(ref_state)
    for k in fused_state:
        a, b = fused_state[k], ref_state[k]
        if hasattr(a, "numpy"):
            np.testing.assert_allclose(
                np.asarray(a.numpy(), np.float64),
                np.asarray(b.numpy(), np.float64),
                atol=BF16_TOL if "bfloat16" in str(a.dtype) else F32_TOL,
                rtol=BF16_TOL, err_msg=k)


def test_build_excludes_stop_gradient_and_missing_grads(fused_flag):
    GLOBAL_FLAGS.set("fused_optimizer", True)
    params = _make_params(MIXED_SPECS[:8], seed=1)
    params[1].stop_gradient = True
    params[3].grad = None
    frozen = [np.asarray(params[i].numpy()).copy() for i in (1, 3)]
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=params)
    opt.step()
    eng = opt._fused_engine
    bucketed = {id(p) for b in eng.buckets for p in b.params}
    assert id(params[1]) not in bucketed
    assert id(params[3]) not in bucketed
    for i, v in zip((1, 3), frozen):
        assert np.array_equal(v, np.asarray(params[i].numpy()))


@pytest.mark.parametrize("shape", [(4, 4), (150, 150)],
                         ids=["inside_a_chunk", "past_a_chunk"])
def test_mid_run_grad_drop_masks_without_rebuild(shape, fused_flag):
    """A param losing its grad mid-run (MoE expert off-route) takes the
    masked-subset path: untouched value AND state, no bucket rebuild.
    The mask is bucket-long (six leaves of 22,500 rest at two chunks)."""
    GLOBAL_FLAGS.set("fused_optimizer", True)
    params = _make_params([(shape, "float32")] * 6, seed=2)
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=params)
    opt.step()
    eng = opt._fused_engine
    buckets0 = list(eng.buckets)
    params[2].grad = None
    before = np.asarray(params[2].numpy()).copy()
    m_before = np.asarray(opt._param_state(params[2])["moment1"])
    m3_before = np.asarray(opt._param_state(params[3])["moment1"])
    opt.step()
    assert np.array_equal(before, np.asarray(params[2].numpy()))
    m_after = np.asarray(opt._param_state(params[2])["moment1"])
    np.testing.assert_array_equal(m_before, m_after)
    # _param_state views are FRESH, not cached copies: a participating
    # param's moment must have moved across the masked step
    m3_after = np.asarray(opt._param_state(params[3])["moment1"])
    assert not np.array_equal(m3_before, m3_after)
    assert eng.buckets == buckets0  # masked, not rebuilt
    b, = eng.buckets
    assert b.masks and all(m.shape == (b.length,) for m in b.masks.values())


@pytest.mark.parametrize("specs", sorted(SPECS))
def test_state_dict_roundtrip_across_paths(specs, fused_flag):
    """fused -> state_dict -> per-param continuation equals a pure
    per-param run; the flat buffers and per-param views are one state,
    and the views keep the parameters' shapes whatever the bucket's
    length."""
    factory, specs = CASES["adam_clip"], SPECS[specs]
    # reference: 3 per-param steps
    _, ref_vals, _, _ = _run(factory, False, specs=specs, steps=3)
    # fused 2 steps, hand off through state_dict to a per-param optimizer
    params, _, _, opt = _run(factory, True, specs=specs, steps=2)
    sd = opt.state_dict()
    for p in params:
        for name in ("moment1", "moment2"):
            assert tuple(sd[f"{p.name}.{name}"].shape) == tuple(p.shape)
    GLOBAL_FLAGS.set("fused_optimizer", False)
    opt2 = factory(params)
    opt2.set_state_dict(sd)
    opt2.step()
    _assert_match(specs,
                  [np.asarray(p.numpy(), np.float64) for p in params],
                  ref_vals)


@pytest.mark.parametrize("specs", sorted(SPECS))
def test_rebuild_reseeds_the_padded_state(specs, fused_flag):
    """A changed parameter set rebuilds the buckets: the new spans are
    seeded from the per-param views of the old ones (offsets, never the
    tail), at the new bucket's length, and the run goes on as the
    per-param loop's does."""
    factory, specs = CASES["adamw_plain"], SPECS[specs]

    def run(fused):
        GLOBAL_FLAGS.set("fused_optimizer", fused)
        params = _make_params(specs)
        held, params[1].grad = params[1].grad, None
        opt = factory(params)
        opt.step()
        opt.step()
        before = list(opt._fused_engine.buckets) if fused else None
        params[1].grad = held  # joins the set: a rebuild, not a mask
        opt.step()
        if fused:
            eng = opt._fused_engine
            assert not set(map(id, eng.buckets)) & set(map(id, before))
            assert id(params[1]) in {id(p) for b in eng.buckets
                                     for p in b.params}
            for b in eng.buckets:
                for flat in b.state.values():
                    assert not _flat_tail(b, flat).any()
        return [np.asarray(p.numpy(), np.float64) for p in params]

    _assert_match(specs, run(True), run(False))


# every update rule the engine carries, with everything that touches the
# flat grads (scale, clip, L1): the tail past the leaves rests at zero
@pytest.mark.parametrize("case", sorted(CASES))
def test_bucket_tail_stays_zero(case, fused_flag, monkeypatch):
    """Ten steps, one of them masked: state spans keep the bucket's length
    and their tail is exactly zero; so is the tail of the new flat params
    (read off the bucket's own jitted update)."""
    params, _, _, opt = _run(CASES[case], True, specs=PAST_A_CHUNK, steps=6)
    held, params[1].grad = params[1].grad, None
    opt.step()  # the masked subset path
    params[1].grad = held
    for _ in range(3):
        opt.step()
    eng = opt._fused_engine
    assert eng.active and len(eng.buckets) == 2
    for b in eng.buckets:
        assert b.length % BUCKET_ALIGN == 0 and b.length > b.total
        for flat in b.state.values():
            assert not _flat_tail(b, flat).any()
        for vec in b.aux.values():
            assert not _flat_tail(b, vec).any()
    # the flat params exist only inside the update: run its body once
    # more, un-jitted, and look at what the concatenation hands it
    import jax
    from paddle_tpu.optimizer import fused as F
    seen = []
    real = F._concat_flat

    def spy(arrays, length):
        seen.append(real(arrays, length))
        return seen[-1]
    monkeypatch.setattr(F, "_concat_flat", spy)
    for b in eng.buckets:
        b.fns.clear()
    with jax.disable_jit():
        opt.step()
    assert len(seen) == 4  # flat p and flat g of two buckets
    for b, flat_p in zip(eng.buckets, seen[::2]):
        assert not _flat_tail(b, flat_p).any()


@pytest.mark.parametrize("accumulate_steps", [1, 2])
def test_trainstep_leaves_the_buckets_to_the_eager_path(accumulate_steps,
                                                         fused_flag):
    """jit.TrainStep never primes or consumes the engine (ISSUE 40: one
    dispatch whatever the optimizer does, so a flat bucket is staging
    for nothing): with the flag on or off it runs one program, state one
    array a leaf, and an eager ``step()`` afterwards builds its buckets
    from that state (tests/test_trainstep_per_leaf.py holds the parity
    and the hand-over at length)."""
    x = paddle.to_tensor(np.random.default_rng(0)
                         .standard_normal((16, 8)).astype(np.float32))

    def build():
        paddle.seed(7)
        m = paddle.nn.Linear(8, 4)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=m.parameters(),
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        step = paddle.jit.TrainStep(m, lambda x: (m(x) ** 2).mean(), opt,
                                    accumulate_steps=accumulate_steps)
        return m, opt, step

    GLOBAL_FLAGS.set("fused_optimizer", True)
    m, opt_f, step_f = build()
    on_losses = [float(step_f(x).numpy()) for _ in range(5)]
    assert opt_f._fused_engine is None
    assert sorted(tuple(v.shape) for v in step_f._opt_state_arrays().values()) \
        == [(4,), (4,), (8, 4), (8, 4)]
    GLOBAL_FLAGS.set("fused_optimizer", False)
    _, _, step_p = build()
    off_losses = [float(step_p(x).numpy()) for _ in range(5)]
    assert on_losses == off_losses          # one program either way
    assert on_losses[-1] < on_losses[0]
    # an eager step takes the leaves' state up into its bucket
    GLOBAL_FLAGS.set("fused_optimizer", True)
    moments = {k: np.asarray(v.numpy()) for k, v in
               opt_f.state_dict().items() if ".moment1" in k}
    (m(x) ** 2).mean().backward()
    opt_f.step()
    eng = opt_f._fused_engine
    b, = eng.buckets
    assert b.total == 36 and b.length > b.total
    assert not _flat_tail(b, b.state["moment1"]).any()
    after = {k: np.asarray(v.numpy()) for k, v in
             opt_f.state_dict().items() if ".moment1" in k}
    assert sorted(v.shape for v in after.values()) == [(4,), (8, 4)]
    for k, v in moments.items():
        assert np.abs(v).max() > 0 and not np.array_equal(after[k], v)
        np.testing.assert_allclose(after[k], 0.9 * v, atol=0.05)


def test_fused_adamw_pallas_kernel_parity():
    """The Pallas bucket kernel (interpret mode) matches the jnp body,
    padding included (n not a multiple of the 128-lane tile)."""
    import jax.numpy as jnp
    from paddle_tpu.kernels.fused_adamw import fused_adamw, _reference

    rng = np.random.default_rng(0)
    n = 1000
    for dt, tol in ((jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)):
        p = jnp.asarray(rng.standard_normal(n), dt)
        g = jnp.asarray(rng.standard_normal(n), dt)
        m = jnp.asarray(rng.standard_normal(n), jnp.float32)
        v = jnp.asarray(np.abs(rng.standard_normal(n)), jnp.float32)
        for decoupled in (True, False):
            out = fused_adamw(p, g, m, v, 0.01, 3, weight_decay=0.05,
                              decoupled=decoupled, block_rows=16,
                              interpret=True)
            ref = _reference(p, g, m, v, 0.01, 1 - 0.9 ** 3, 1 - 0.999 ** 3,
                             beta1=0.9, beta2=0.999, eps=1e-8, wd=0.05,
                             decoupled=decoupled)
            for a, b in zip(out, ref):
                np.testing.assert_allclose(
                    np.asarray(a, np.float64), np.asarray(b, np.float64),
                    atol=tol, rtol=tol)


@pytest.mark.parametrize("specs", [[((8, 16), "float32")] * 4, PAST_A_CHUNK],
                         ids=["inside_a_chunk", "past_a_chunk"])
def test_engine_uses_pallas_kernel_when_forced(specs, fused_flag,
                                               monkeypatch):
    """PADDLE_TPU_FORCE_PALLAS=1 routes the AdamW bucket update through the
    Pallas kernel (interpreter on CPU) with unchanged numerics; the bucket
    arrives at the kernel's alignment, so its wrapper pads nothing."""
    from paddle_tpu.kernels import fused_adamw as K
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    lengths = []
    real = K._run

    def spy(p, *args, **kw):
        lengths.append(p.shape[0])
        return real(p, *args, **kw)
    monkeypatch.setattr(K, "_run", spy)

    def factory(ps):
        return paddle.optimizer.AdamW(learning_rate=0.01, parameters=ps,
                                      weight_decay=0.01)

    _, forced_vals, _, _ = _run(factory, True, specs=specs, steps=2)
    assert lengths and all(n % BUCKET_ALIGN == 0 for n in lengths)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS")
    _, ref_vals, _, _ = _run(factory, False, specs=specs, steps=2)
    _assert_match(specs, forced_vals, ref_vals)


def test_opt_out_flag_restores_per_param_loop(fused_flag):
    GLOBAL_FLAGS.set("fused_optimizer", False)
    params = _make_params(MIXED_SPECS[:4], seed=3)
    opt = paddle.optimizer.Adam(learning_rate=0.01, parameters=params)
    opt.step()
    assert opt._fused_engine is None
