"""A compiled train step updates every leaf where it lies (ISSUE 40).

Inside ``jit.TrainStep`` there is one dispatch whatever the optimizer
does, so the flat buckets of ``optimizer/fused.py`` (the eager path's:
O(#buckets) dispatches) buy nothing there and cost a pass over HBM a
ravel, a concatenation and a cut, and under a mesh a gather of every
shard. Held here, on the CPU:

(a) the compiled text is the witness: nothing of a flat bucket under
    ``phase.optimizer``, state one array a leaf, on a data x tensor
    mesh (``dp=4,tp=2``: the CPU host's eight devices, the four-chip
    cell's ``dp=2,tp=2`` with two more data ranks) no collective under
    the phase and every moment where its parameter lives;
(b) ten compiled steps equal ten eager ones (the eager side runs the
    flat engine), parameters and moments, over the optimizers, clips and
    decays the engine carries, a leaf that gets no gradient in some
    steps, accumulated micro-batches and a loss scaler;
(c) a model carries its moments across every hand-over between the two
    forms and through ``state_dict``;
(d) which form runs follows the gradients (tracers or not), never
    ``FLAGS_fused_optimizer``, and the Pallas bucket kernel is not
    reached from a compiled step.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.distributed import gspmd
from paddle_tpu.profiler import phases

from trainstep_witness import (collectives_under_optimizer,
                               flat_bucket_traces, smollm2_like_step)

TOL = dict(rtol=2e-5, atol=2e-6)


@pytest.fixture
def fused_flag():
    yield
    GLOBAL_FLAGS.set("fused_optimizer", True)


@pytest.fixture(scope="module", autouse=True)
def _fresh_compiles():
    """These tests read scopes out of compiled text; JAX's persistent
    cache leaves metadata out of its key (tests/test_program_phases.py)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# --- (a) the compiled text ---------------------------------------------------

@pytest.fixture(scope="module")
def one_device():
    step, model, opt, ids = smollm2_like_step()
    losses = [float(step(ids).numpy()) for _ in range(3)]
    return step, opt, losses, phases.text(phases.newest("train.step"))


@pytest.fixture(scope="module")
def dp_x_tp():
    if len(jax.devices()) != 8:
        pytest.skip("needs the 8-device CPU mesh")
    step, model, opt, ids = smollm2_like_step(sharding="dp=4,tp=2")
    losses = [float(step(ids).numpy()) for _ in range(3)]
    return step, opt, losses


def _leaves(step):
    return sum(p._data.size for p in step._params.values())


def test_no_flat_bucket_is_built_inside_the_step(one_device):
    step, _, losses, text = one_device
    assert losses[-1] < losses[0]
    assert "phase.optimizer" in text
    assert not flat_bucket_traces(text, _leaves(step))


def test_the_witness_sees_a_flat_bucket_where_there_is_one():
    """The guard above can see what it guards against: the eager bucket
    update, compiled, concatenates, cuts and holds bucket-sized results."""
    import jax.numpy as jnp
    params = []
    for shape in ((64, 128), (128, 64), (128,)):
        t = paddle.to_tensor(np.zeros(shape, np.float32))
        t.stop_gradient = False
        params.append(t)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=params)
    assert opt._prime_fused(params)
    bucket, = opt._fused_engine.buckets
    fn = opt._fused_engine._bucket_fn(bucket, use_scale=False, donate=False,
                                      use_mask=False)
    leaves = tuple(p._data for p in params)

    def scoped(*a):
        with phases.phase("optimizer"):
            return fn(*a)
    text = jax.jit(scoped).lower(
        leaves, leaves, bucket.state, bucket.aux, jnp.float32(1e-4),
        jnp.int32(1), jnp.float32(1.0), jnp.float32(1.0)).compile().as_text()
    found = flat_bucket_traces(text, bucket.total)
    assert any(f.startswith("concatenate") for f in found), found


def test_state_rests_one_array_a_leaf_beside_it(one_device):
    step, opt, _, _ = one_device
    eng = opt._fused_engine
    assert eng is None or not eng.active
    state = step._opt_state_arrays()
    assert len(state) == 2 * len(step._params)
    for key, p in step._params.items():
        for name in ("moment1", "moment2"):
            assert state[f"{key}.{name}"].shape == p._data.shape


def test_a_sharded_step_gathers_nothing_for_the_optimizer(dp_x_tp, one_device):
    step, _, losses, = dp_x_tp
    np.testing.assert_allclose(losses, one_device[2], rtol=2e-3)
    assert step.last_hlo_collectives["all_reduce"] > 0
    assert not collectives_under_optimizer(step.last_hlo_text)
    assert not flat_bucket_traces(step.last_hlo_text, _leaves(step))


def test_a_moment_lives_where_its_parameter_lives(dp_x_tp):
    step, opt, _ = dp_x_tp
    sharded = 0
    for p in step._params.values():
        spec = p._data.sharding.spec
        sharded += gspmd.MODEL_AXIS in spec
        for v in opt._state[id(p)].values():
            assert v.sharding.spec == spec
            assert v.addressable_shards[0].data.shape == \
                p._data.addressable_shards[0].data.shape
    assert sharded >= 7 * 2    # q, k, v, o, gate, up, down a layer


# --- (b) parity with the eager path -----------------------------------------

def _net(two_heads=False):
    paddle.seed(5)
    trunk = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.Tanh())
    head = paddle.nn.Linear(16, 4)
    side = paddle.nn.Linear(16, 4) if two_heads else None
    layers = [trunk, head] + ([side] if two_heads else [])
    params = [p for l in layers for p in l.parameters()]
    for i, p in enumerate(params):
        p.name = f"w{i}"

    def loss_fn(x, y):
        h = trunk(x)
        out = head(h)
        if side is not None and x.shape[0] == 8:    # the wide batches only
            out = out + side(h)
        return ((out - y) ** 2).mean()
    return params, loss_fn, paddle.nn.LayerList(layers)


def _batches(n, rows=lambda i: 8):
    rng = np.random.default_rng(3)
    return [(paddle.to_tensor(rng.standard_normal((rows(i), 8))
                              .astype(np.float32)),
             paddle.to_tensor(rng.standard_normal((rows(i), 4))
                              .astype(np.float32))) for i in range(n)]


L = paddle.nn
O = paddle.optimizer
CASES = {
    "adamw_decay_fun_lr_ratio": dict(opt=lambda ps: O.AdamW(
        learning_rate=0.01, parameters=ps, weight_decay=0.05,
        apply_decay_param_fun=lambda n: not n.endswith("1"),
        lr_ratio=lambda p: 0.5 if p.name.endswith("2") else 1.0)),
    "adamw_plain": dict(opt=lambda ps: O.AdamW(
        learning_rate=0.01, parameters=ps, weight_decay=0.05)),
    "adam_l2": dict(opt=lambda ps: O.Adam(
        learning_rate=0.01, parameters=ps, weight_decay=0.02)),
    "momentum_nesterov": dict(opt=lambda ps: O.Momentum(
        learning_rate=0.05, momentum=0.9, use_nesterov=True, parameters=ps,
        weight_decay=0.01)),
    "sgd_l2": dict(opt=lambda ps: O.SGD(
        learning_rate=0.1, parameters=ps, weight_decay=0.01)),
    "adamw_clip_global_norm": dict(opt=lambda ps: O.AdamW(
        learning_rate=0.01, parameters=ps,
        grad_clip=L.ClipGradByGlobalNorm(0.05))),
    "adamw_clip_by_value": dict(opt=lambda ps: O.AdamW(
        learning_rate=0.01, parameters=ps,
        grad_clip=L.ClipGradByValue(0.01))),
    "sgd_l1": dict(opt=lambda ps: O.SGD(
        learning_rate=0.1, parameters=ps,
        weight_decay=paddle.regularizer.L1Decay(0.01))),
    "adam_l1_clip": dict(opt=lambda ps: O.Adam(
        learning_rate=0.01, parameters=ps,
        weight_decay=paddle.regularizer.L1Decay(0.01),
        grad_clip=L.ClipGradByGlobalNorm(0.05))),
    "a_leaf_without_a_gradient_in_some_steps": dict(
        opt=lambda ps: O.AdamW(learning_rate=0.01, parameters=ps,
                               weight_decay=0.05),
        two_heads=True, rows=lambda i: 8 if i % 3 == 0 else 4),
    "accumulate_two_micro_batches": dict(
        opt=lambda ps: O.AdamW(learning_rate=0.01, parameters=ps,
                               grad_clip=L.ClipGradByGlobalNorm(0.05)),
        accumulate_steps=2),
    "momentum_accumulate_two": dict(
        opt=lambda ps: O.Momentum(learning_rate=0.05, momentum=0.9,
                                  parameters=ps),
        accumulate_steps=2),
}


def _snapshot(params, opt):
    state = {k: np.asarray(v.numpy(), np.float64)
             for k, v in opt.state_dict().items()
             if hasattr(v, "numpy")}
    return [np.asarray(p.numpy(), np.float64) for p in params], state


def _assert_equal(got, want, **tol):
    for a, b in zip(got[0], want[0]):
        np.testing.assert_allclose(a, b, **(tol or TOL))
    assert got[1].keys() == want[1].keys()
    for k in want[1]:
        np.testing.assert_allclose(got[1][k], want[1][k], err_msg=k,
                                   **(tol or TOL))


def _eager_steps(params, loss_fn, opt, batches):
    for x, y in batches:
        loss_fn(x, y).backward()
        opt.step()
        opt.clear_grad()


@pytest.mark.parametrize("case", sorted(CASES))
def test_ten_compiled_steps_equal_ten_eager_steps(case):
    spec = CASES[case]
    batches = _batches(10, spec.get("rows", lambda i: 8))
    two = spec.get("two_heads", False)

    params, loss_fn, _ = _net(two)
    opt = spec["opt"](params)
    _eager_steps(params, loss_fn, opt, batches)
    if opt._state_schema(params[0]):
        assert opt._fused_engine.active    # the eager side ran flat
    want = _snapshot(params, opt)

    params, loss_fn, net = _net(two)
    opt = spec["opt"](params)
    step = paddle.jit.TrainStep(
        net, loss_fn, opt, accumulate_steps=spec.get("accumulate_steps", 1))
    for x, y in batches:
        step(x, y)
    assert opt._fused_engine is None
    _assert_equal(_snapshot(params, opt), want)


def test_a_leaf_without_a_gradient_is_skipped_not_masked():
    """Off-route experts, frozen leaves: the flat form masked their spans;
    per leaf the step does not touch them and their state rides through."""
    params, loss_fn, net = _net(two_heads=True)
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=params,
                                 weight_decay=0.05)
    step = paddle.jit.TrainStep(net, loss_fn, opt)
    wide, narrow = _batches(2, lambda i: (8, 4)[i])
    step(*wide)
    side = params[-2:]
    before = [(np.asarray(p.numpy()),
               {k: np.asarray(v) for k, v in opt._state[id(p)].items()})
              for p in side]
    assert all(np.abs(st["moment1"]).max() > 0 for _, st in before)
    step(*narrow)
    step(*narrow)
    for p, (data, st) in zip(side, before):
        np.testing.assert_array_equal(np.asarray(p.numpy()), data)
        for k, v in st.items():
            np.testing.assert_array_equal(
                np.asarray(opt._state[id(p)][k]), v)
    trunk_m = opt._state[id(params[0])]["moment1"]
    step(*wide)
    assert not np.array_equal(np.asarray(opt._state[id(side[0])]["moment1"]),
                              before[0][1]["moment1"])
    assert np.abs(np.asarray(trunk_m)).max() > 0


# --- (c) hand-over -----------------------------------------------------------

HOPS = {
    "eager_compiled_eager": ("eager", "compiled", "fresh", "eager"),
    "compiled_eager_compiled": ("compiled", "eager", "fresh", "compiled"),
    "eager_fresh_compiled_eager": ("eager", "fresh", "compiled", "eager"),
    "compiled_scaler_compiled": ("compiled", "scaler", "compiled"),
}
OPTS = {
    "adamw": lambda ps: O.AdamW(learning_rate=0.01, parameters=ps,
                                weight_decay=0.05),
    "momentum": lambda ps: O.Momentum(learning_rate=0.05, momentum=0.9,
                                      parameters=ps),
}


@pytest.mark.parametrize("make", sorted(OPTS))
@pytest.mark.parametrize("hops", sorted(HOPS))
def test_moments_cross_every_hand_over(hops, make, fused_flag):
    """Three steps a hop. ``eager``: ``opt.step()``, flat state live in
    the engine; ``compiled``: the same TrainStep again, per-leaf state;
    ``fresh``: ``state_dict()`` into a new optimizer (and a new
    TrainStep); ``scaler``: eager steps under a ``GradScaler`` (its skip
    decision is a host read, so it cannot be traced: it meets a compiled
    step only across a hand-over). Against the per-parameter loop alone
    (``FLAGS_fused_optimizer`` off), at every hop."""
    batches = _batches(12)

    GLOBAL_FLAGS.set("fused_optimizer", False)
    ref_params, ref_loss, _ = _net()
    ref_opt = OPTS[make](ref_params)
    GLOBAL_FLAGS.set("fused_optimizer", True)

    params, loss_fn, net = _net()
    opt = OPTS[make](params)
    step = paddle.jit.TrainStep(net, loss_fn, opt)
    scaler = paddle.amp.GradScaler(init_loss_scaling=64.0)
    at = 0
    for hop in HOPS[hops]:
        if hop == "fresh":
            state = opt.state_dict()
            opt = OPTS[make](params)
            opt.set_state_dict(state)
            step = paddle.jit.TrainStep(net, loss_fn, opt)
            _assert_equal(_snapshot(params, opt),
                          _snapshot(ref_params, ref_opt))
            continue
        todo = batches[at:at + 3]
        at += 3
        GLOBAL_FLAGS.set("fused_optimizer", False)
        _eager_steps(ref_params, ref_loss, ref_opt, todo)
        GLOBAL_FLAGS.set("fused_optimizer", True)
        if hop == "eager":
            _eager_steps(params, loss_fn, opt, todo)
            assert opt._fused_engine.active
        elif hop == "scaler":
            for x, y in todo:
                scaler.scale(loss_fn(x, y)).backward()
                scaler.step(opt)
                scaler.update()
                opt.clear_grad()
            assert opt._fused_engine.active
        else:
            for x, y in todo:
                step(x, y)
            assert not opt._fused_engine or not opt._fused_engine.active
        assert opt._step_count == ref_opt._step_count == at
        _assert_equal(_snapshot(params, opt), _snapshot(ref_params, ref_opt))


# --- (d) what decides the form ----------------------------------------------

@pytest.mark.parametrize("flag", [True, False])
def test_the_compiled_step_does_not_read_the_flag(flag, fused_flag):
    GLOBAL_FLAGS.set("fused_optimizer", flag)
    params, loss_fn, net = _net()
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=params)
    step = paddle.jit.TrainStep(net, loss_fn, opt, capture_hlo=True)
    step(*_batches(1)[0])
    assert opt._fused_engine is None
    assert len(step._opt_state_arrays()) == 2 * len(params)
    assert not flat_bucket_traces(step.last_hlo_text,
                                  sum(p._data.size for p in params))
    # the eager path goes on meaning what the flag says
    x, y = _batches(1)[0]
    loss_fn(x, y).backward()
    opt.step()
    assert (opt._fused_engine is not None
            and opt._fused_engine.active) == flag


def test_a_compiled_step_never_reaches_the_bucket_kernel(monkeypatch):
    """Forced onto the Pallas path (how CPU CI runs the kernel), an eager
    step calls ``fused_adamw`` once a bucket; a compiled step, on one
    device or a mesh, never does."""
    import paddle_tpu.kernels.fused_adamw as K
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")
    calls = []
    real = K.fused_adamw
    monkeypatch.setattr(K, "fused_adamw",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    for sharding in (None, "dp=4,tp=2"):
        params, loss_fn, net = _net()
        opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=params)
        step = paddle.jit.TrainStep(net, loss_fn, opt, sharding=sharding)
        step(*_batches(1)[0])
        assert not calls
    params, loss_fn, _ = _net()
    opt = paddle.optimizer.AdamW(learning_rate=0.01, parameters=params)
    x, y = _batches(1)[0]
    loss_fn(x, y).backward()
    opt.step()
    assert len(calls) == 1
