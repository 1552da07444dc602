"""GSPMD-native sharding gates (distributed/gspmd.py, ISSUE 10).

The multi-device CPU lane: conftest.py forces an 8-device virtual CPU
mesh (``--xla_force_host_platform_device_count=8``), so every regime is
provable chip-free. The acceptance bars, asserted not logged:

- DP/TP/ZeRO presets are ANNOTATIONS ONLY: the same TrainStep call with
  a different preset string produces loss bit-comparable (<= 1e-6) to
  the single-device reference — no per-regime step code;
- optimizer state rests one array a leaf where its parameter lives;
  under the ZeRO preset it is split over the data axis besides
  (per-device state = global/degree) with matching in/out shardings
  (the donation-validity condition);
- the collective mix read from the compiled HLO matches what each
  preset promises (DP: grad all-reduce, no gathers; ZeRO: param
  all-gather appears; TP: strictly more all-reduces than DP);
- the tensor-parallel serving engine keeps the ragged-step trace count
  at 1 with the KV pool sharded over the model (kv-head) axis, token
  identical to the single-device engine (fp AND int8 pools);
- sharded params round-trip through distributed/checkpoint.py across a
  DIFFERENT destination mesh layout (reshard-on-load);
- FLAGS_gspmd follows the on_set-rollback validation pattern.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import jit as pjit
from paddle_tpu.core.flags import GLOBAL_FLAGS
from paddle_tpu.distributed import gspmd
from paddle_tpu.models import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.serving import LLMEngine

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")

CFG = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
           num_attention_heads=4, num_key_value_heads=2, vocab_size=256)
PRESETS = ["dp=8", "tp=2,dp=4", "tp=4,dp=2", "dp=8,zero"]


def _train(preset, n_steps=3):
    """ONE training function for every regime: the preset string is the
    only thing that changes between runs — that IS the tentpole's
    contract (annotations, not per-regime code paths)."""
    cfg = llama_tiny_config(**CFG)
    paddle.seed(7)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    def loss_fn(ids):
        logits = model(ids)
        return F.cross_entropy(
            logits[:, :-1].reshape((-1, cfg.vocab_size)),
            ids[:, 1:].reshape((-1,)))

    step = pjit.TrainStep(model, loss_fn, opt, sharding=preset)
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(n_steps):
        b = rng.integers(0, cfg.vocab_size, (8, 16))
        losses.append(float(step(paddle.to_tensor(b)).numpy()))
    return losses, step, opt


@pytest.fixture(scope="module")
def runs():
    out = {None: _train(None)}
    for preset in PRESETS:
        out[preset] = _train(preset)
    return out


# ---------------------------------------------------------------------------
# training: preset parity, annotations only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("preset", PRESETS)
def test_preset_loss_parity_vs_single_device(runs, preset):
    ref = runs[None][0]
    got = runs[preset][0]
    assert max(abs(a - b) for a, b in zip(ref, got)) <= 1e-6, (
        f"{preset}: {got} vs reference {ref}")


def test_zero_shards_optimizer_state_a_leaf_at_a_time(runs):
    """ZeRO-1 per leaf (ISSUE 40 took the flat buckets out of the compiled
    step): every moment is split over the data axis on the first
    dimension its parameter leaves whole and the degree divides, so a
    data rank holds 1/dp of it; the parameters stay replicated."""
    _, step, opt = runs["dp=8,zero"]
    assert opt._fused_engine is None
    arrs = step._opt_state_arrays()
    assert len(arrs) == 2 * len(step._params)
    dp = 8
    held = whole = 0
    for k, v in arrs.items():
        p = step._params[k.split(".", 1)[0]]._data
        assert p.sharding.spec == P() and v.shape == p.shape
        sh = v.sharding
        assert isinstance(sh, NamedSharding), (k, sh)
        local = v.addressable_shards[0].data
        whole += v.size
        held += local.size
        if any(d % dp == 0 for d in v.shape):
            d = next(i for i, n in enumerate(v.shape) if n % dp == 0)
            assert sh.spec[d] == gspmd.DATA_AXIS, (k, sh.spec)
            # per-device state memory really is global/degree
            assert local.shape[d] == v.shape[d] // dp, (k, local.shape)
    assert held * dp == whole       # every leaf of this model divides
    # donation-validity condition: the state coming OUT of the step has
    # exactly the sharding the step takes IN (identical in/out specs)
    mesh = step._mesh
    p_sh = {k: p._data.sharding for k, p in step._params.items()}
    o_sh = gspmd.opt_state_shardings(arrs, p_sh, mesh, zero=True)
    for k, v in arrs.items():
        assert v.sharding.spec == o_sh[k].spec


def test_tp_state_lives_where_its_parameter_lives(runs):
    """``tp=2,dp=4``: every moment's sharding equals its parameter's, so
    each chip updates the half of a tensor-parallel leaf that it holds
    and no state array is replicated that its parameter is not."""
    _, step, opt = runs["tp=2,dp=4"]
    assert opt._fused_engine is None
    arrs = step._opt_state_arrays()
    assert len(arrs) == 2 * len(step._params)
    sharded = 0
    for k, v in arrs.items():
        p = step._params[k.split(".", 1)[0]]._data
        assert v.sharding.spec == p.sharding.spec, (k, v.sharding.spec)
        assert v.addressable_shards[0].data.shape == \
            p.addressable_shards[0].data.shape
        sharded += gspmd.MODEL_AXIS in p.sharding.spec
    assert sharded == 2 * (2 * 7 + 2)   # 7 a layer, embed, lm_head


def test_tp_shards_params_on_model_axis(runs):
    _, step, opt = runs["tp=2,dp=4"]
    by_name = {step._param_names[k]: p._data
               for k, p in step._params.items()}
    q = by_name["model.layers.0.self_attn.q_proj.weight"]
    o = by_name["model.layers.0.self_attn.o_proj.weight"]
    ln = by_name["model.layers.0.input_layernorm.weight"]
    assert q.sharding.spec == P(None, gspmd.MODEL_AXIS)
    assert o.sharding.spec == P(gspmd.MODEL_AXIS, None)
    assert ln.sharding.spec == P()
    emb = by_name["model.embed_tokens.weight"]
    assert emb.sharding.spec == P(gspmd.MODEL_AXIS, None)   # vocab axis


def test_collective_mix_matches_preset(runs):
    cc = {p: runs[p][1].last_hlo_collectives for p in PRESETS}
    assert runs[None][1].last_hlo_collectives is None   # no mesh, no HLO
    # DP: the grad sync is all-reduce; nothing needs gathering
    assert cc["dp=8"]["all_reduce"] > 0
    assert cc["dp=8"]["all_gather"] == 0
    # ZeRO: the updated params reassemble from the sharded state
    assert cc["dp=8,zero"]["all_gather"] > 0
    # TP: every row-parallel projection adds a psum on top of DP's sync
    for tp in ("tp=2,dp=4", "tp=4,dp=2"):
        assert cc[tp]["all_reduce"] > cc["dp=8"]["all_reduce"], (tp, cc)


def test_training_continues_after_first_compile(runs):
    # losses strictly change step to step: the sharded executable keeps
    # training (no stale-param reuse), for every preset — and it is ONE
    # executable: the annotations do not re-key the jit between steps
    for preset, (losses, step, _) in runs.items():
        assert len(set(losses)) == len(losses), (preset, losses)
        assert len(step._cache) == 1, preset


# ---------------------------------------------------------------------------
# serving: tensor-parallel engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_model():
    paddle.seed(11)
    return LlamaForCausalLM(llama_tiny_config(**CFG))


def _serve(model, mesh, **kw):
    shared = [7] * 8
    prompts = [shared + [1, 2, 3], shared + [1, 9],
               shared + [4, 5, 6, 7]]
    eng = LLMEngine(model, max_len=64, page_size=8, max_num_seqs=4,
                    mesh=mesh, **kw)
    rids = [eng.add_request(prompts[0], max_new_tokens=6, seed=3)]
    eng.step(); eng.step(); eng.step()      # donor prompt committed
    for p in prompts[1:]:
        rids.append(eng.add_request(p, max_new_tokens=6, seed=4))
    eng.run(max_steps=300)
    eng.pool.check_invariants()
    return [eng.outputs()[r].token_ids for r in rids], eng


@pytest.mark.parametrize("kw", [
    {},
    dict(kv_cache_dtype="int8", quantized_mode="weight_only_int8"),
], ids=["fp", "int8"])
def test_tp_engine_token_identity_and_trace_count(serve_model, kw):
    ref, _ = _serve(serve_model, None, **kw)
    out, eng = _serve(serve_model, 2, **kw)
    assert out == ref, "tensor-parallel engine diverged from 1-device"
    # THE serving gate: the one fixed-shape ragged executable, compiled
    # once, under the mesh — prefix forks, CoW and frees included
    assert eng.decode_cache_size() == 1
    assert eng.metrics_snapshot()["model_parallel_degree"] == 2
    # the pool's pages (and int8 scale rows) shard on the kv-head axis
    # and STAY sharded across steps (sharding inference round-trips)
    K0 = eng.pool.kv[0][0]
    assert K0.sharding.spec[0] == gspmd.MODEL_AXIS
    assert K0.addressable_shards[0].data.shape[0] == K0.shape[0] // 2
    if eng.pool.kv_scales is not None:
        Ks = eng.pool.kv_scales[0][0]
        assert Ks.sharding.spec[0] == gspmd.MODEL_AXIS
    assert eng.pool.kv_bytes_per_token_per_device == \
        eng.pool.kv_bytes_per_token / 2


def test_tp_engine_rejects_indivisible_kv_heads(serve_model):
    paddle.seed(3)
    odd = LlamaForCausalLM(llama_tiny_config(
        **{**CFG, "num_attention_heads": 3, "num_key_value_heads": 3,
           "hidden_size": 48, "intermediate_size": 96}))
    with pytest.raises(ValueError, match="kv heads"):
        LLMEngine(odd, max_len=64, page_size=8, mesh=2)


# ---------------------------------------------------------------------------
# checkpoint: sharded save -> reshard-on-load
# ---------------------------------------------------------------------------

def test_sharded_params_checkpoint_roundtrip(tmp_path):
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed.checkpoint import (load_state_dict,
                                                   save_state_dict)
    src_mesh = gspmd.build_mesh(gspmd.ShardingConfig(data=2, model=4))
    dst_mesh = gspmd.build_mesh(gspmd.ShardingConfig(data=4, model=2))
    rng = np.random.default_rng(0)
    vals = {
        "q": rng.standard_normal((16, 32)).astype(np.float32),
        "o": rng.standard_normal((32, 16)).astype(np.float32),
        "ln": rng.standard_normal((16,)).astype(np.float32),
    }
    specs = {"q": P(None, gspmd.MODEL_AXIS),
             "o": P(gspmd.MODEL_AXIS, None), "ln": P()}
    src = {k: Tensor(jax.device_put(
        jnp.asarray(v), NamedSharding(src_mesh, specs[k])))
        for k, v in vals.items()}
    save_state_dict(src, str(tmp_path / "ckpt"))
    dst = {k: Tensor(jax.device_put(
        jnp.zeros_like(jnp.asarray(v)), NamedSharding(dst_mesh, specs[k])))
        for k, v in vals.items()}
    load_state_dict(dst, str(tmp_path / "ckpt"))
    for k, v in vals.items():
        got = np.asarray(dst[k]._data)
        np.testing.assert_array_equal(got, v)
        # the DESTINATION layout survived the load (reshard, not
        # replace): still sharded on the destination mesh
        assert dst[k]._data.sharding.spec == specs[k]
        if specs[k] != P():
            assert len(dst[k]._data.sharding.device_set) == 8


# ---------------------------------------------------------------------------
# flags / config validation
# ---------------------------------------------------------------------------

def test_flags_gspmd_on_set_rollback():
    old = GLOBAL_FLAGS.get("gspmd")
    with pytest.raises(ValueError):
        GLOBAL_FLAGS.set("gspmd", "bogus=2x")
    assert GLOBAL_FLAGS.get("gspmd") == old, (
        "a rejected preset must roll the flag back (on_set contract)")
    GLOBAL_FLAGS.set("gspmd", "tp=2,dp=4,zero")
    try:
        cfg = gspmd.config_from_flags()
        assert (cfg.data, cfg.model, cfg.zero) == (4, 2, True)
    finally:
        GLOBAL_FLAGS.set("gspmd", old)


def test_sharding_config_validation():
    with pytest.raises(ValueError):
        gspmd.ShardingConfig(model=0)
    with pytest.raises(ValueError):
        gspmd.ShardingConfig(data=-2)
    with pytest.raises(ValueError):
        gspmd.ShardingConfig(data=3, model=3).resolve(8)
    with pytest.raises(ValueError):
        gspmd.ShardingConfig(model=3).resolve(8)   # 3 does not divide 8
    cfg = gspmd.ShardingConfig(model=2).resolve(8)
    assert (cfg.data, cfg.model) == (4, 2)
    assert gspmd.ShardingConfig.parse("") is None


def test_flags_gspmd_drives_trainstep(runs):
    """The flag route (no explicit ShardingConfig argument) is the same
    annotation path: FLAGS_gspmd=dp=8 reproduces the reference losses."""
    old = GLOBAL_FLAGS.get("gspmd")
    GLOBAL_FLAGS.set("gspmd", "dp=8")
    try:
        losses, step, _ = _train(None, n_steps=2)
    finally:
        GLOBAL_FLAGS.set("gspmd", old)
    ref = runs[None][0][:2]
    assert max(abs(a - b) for a, b in zip(ref, losses)) <= 1e-6
    assert step.last_hlo_collectives is not None
