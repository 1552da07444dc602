"""The main path's Pallas kernels, compiled for a DESCRIBED v5e at
TinyLlama-1.1B widths (hidden 2048, 32 q / 4 kv heads, head_dim 64, ffn
5632, page 16) — rehearsal 3 of the on-chip-measurement guide kept as
tests, so what the chip's compiler refuses fails here, at no chip time.

Nothing runs: a compile that passes is not a chip run. This is the only
test file that describes a topology, and it does so inside a
module-scoped fixture (never at import: one process at a time may load
the TPU's library, and every xdist worker imports every test file).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# TinyLlama-1.1B geometry
D, H, HKV, DH, FFN, PS = 2048, 32, 4, 64, 5632, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep these off it, and
    # compile at the chip path's matmul precision, not conftest's
    # "highest"
    saved_cache = jax.config.jax_enable_compilation_cache
    saved_prec = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.config.update("jax_default_matmul_precision", None)
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_default_matmul_precision", saved_prec)
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The kernels' entry points ask the shared backend probe and take
    their jnp branch on this host; steer it here, in the test."""
    import paddle_tpu.kernels as K
    monkeypatch.setattr(K, "_ON_TPU", True)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)


def _compiled_text(fn, one_chip, *shapes, donate=()):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile() \
        .as_text()


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---- one builder per kernel: returns (fn, shapes, expected custom calls)

def _flash(dh, heads, bwd):
    from paddle_tpu.kernels.flash_attention import flash_attention
    q, kv = _s((1, heads, 2048, dh)), _s((1, HKV, 2048, dh))
    if not bwd:
        return (lambda q, k, v: flash_attention(q, k, v, causal=True),
                (q, kv, kv), 1)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True) \
            .astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv), 3


def _rms_norm():
    from paddle_tpu.kernels.rms_norm import rms_norm

    def loss(x, w):
        return rms_norm(x, w).astype(jnp.float32).sum()
    return (jax.value_and_grad(loss, argnums=(0, 1)),
            (_s((2048, D)), _s((D,))), 2)


def _pool(num_pages, dtype=jnp.bfloat16):
    return _s((HKV, num_pages, PS, DH), dtype)


def _paged():
    from paddle_tpu.kernels.paged_attention import paged_attention
    R, pages, pps = 8, 1024, 128
    return (lambda q, k, v, t, l: paged_attention(q, k, v, t, l),
            (_s((R, H, DH)), _pool(pages), _pool(pages),
             _s((R, pps), jnp.int32), _s((R,), jnp.int32)), 1)


def _ragged(T, q_block, int8_kv, *, h=H, hkv=HKV, dh=DH, R=8, pages=1024,
            pps=128, window=None):
    from paddle_tpu.kernels.paged_attention import ragged_paged_attention
    i32 = jnp.int32
    pool = _s((hkv, pages, PS, dh), jnp.int8 if int8_kv else jnp.bfloat16)
    shapes = [_s((T, h, dh)), pool, pool,
              _s((R, pps), i32), _s((R,), i32), _s((R,), i32),
              _s((R,), i32)]
    if int8_kv:
        shapes += [_s((hkv, pages), jnp.float32)] * 2

        def fn(q, k, v, t, qs, ql, kl, ks, vs):
            return ragged_paged_attention(q, k, v, t, qs, ql, kl,
                                          q_block=q_block, k_scales=ks,
                                          v_scales=vs)
    else:
        def fn(q, k, v, t, qs, ql, kl):
            return ragged_paged_attention(q, k, v, t, qs, ql, kl,
                                          q_block=q_block, window=window)
    return fn, tuple(shapes), 1


def _append(T, *, hkv=HKV, dh=DH, pages=1024, dtype=jnp.bfloat16):
    from paddle_tpu.kernels.paged_attention import kv_append
    return (lambda P, slot, x: kv_append(P, slot, x),
            (_s((hkv, pages, PS, dh), dtype), _s((T,), jnp.int32),
             _s((hkv, T, dh), dtype)), 1)


def _grouped(k, n, held=16, rows=6144):
    """A projection of the routed experts at K-EXAONE's widths: 512
    token slots x 8 picks + a partly filled tile an expert = 6,144 rows."""
    from paddle_tpu.kernels.grouped_matmul import grouped_matmul

    def fn(x, w, tile_group, live):
        return grouped_matmul(x, w, tile_group, live)
    return fn, (_s((rows, k)), _s((held, k, n)),
                _s((rows // 128,), jnp.int32), _s((), jnp.int32)), 1


def _adamw(n, dtype):
    from paddle_tpu.kernels.fused_adamw import fused_adamw
    f32 = jnp.float32

    def fn(p, g, m, v, lr, t):
        return fused_adamw(p, g, m, v, lr, t, weight_decay=0.01)
    return fn, (_s((n,), dtype), _s((n,), dtype), _s((n,), f32),
                _s((n,), f32), _s((), f32), _s((), jnp.int32)), 1


def _dequant(bits):
    from paddle_tpu.kernels.int8_matmul import dequant_matmul
    rows = D // 2 if bits == 4 else D

    def fn(x, w, s):
        return dequant_matmul(x, w, s, rows=D, bits=bits)
    return fn, (_s((8, D)), _s((rows, FFN), jnp.int8),
                _s((1, FFN), jnp.float32)), 1


# a head_dim-128 toy geometry the megakernels' whole-matrix-in-VMEM and
# lane-reshape design does fit: pins what IS legal (the row tiling)
# apart from what the TinyLlama widths still trip
SMALL = dict(d=512, h=4, hkv=2, dh=128, ffn=1024)
TINYLLAMA = dict(d=D, h=H, hkv=HKV, dh=DH, ffn=FFN)


def _layer_shapes(d, h, hkv, dh, ffn):
    w = {"q": (d, h * dh), "k": (d, hkv * dh), "v": (d, hkv * dh),
         "o": (h * dh, d), "gate": (d, ffn), "up": (d, ffn),
         "down": (ffn, d)}
    layer = {k: _s(v) for k, v in w.items()}
    layer["ln1"] = _s((d,))
    layer["ln2"] = _s((d,))
    return layer


def _decode_layer(d, h, hkv, dh, ffn):
    from paddle_tpu.kernels.decode_megakernel import fused_decode_layer
    R, pages, pps = 8, 1024, 128
    pool = _s((hkv, pages, PS, dh))

    def fn(layer, hid, kp, vp, tbl, kl):
        return fused_decode_layer(layer, hid, kp, vp, tbl, kl, eps=1e-5,
                                  theta=10000.0, num_heads=h)
    return fn, (_layer_shapes(d, h, hkv, dh, ffn), _s((R, d)), pool, pool,
                _s((R, pps), jnp.int32), _s((R,), jnp.int32)), 1


def _decode_model(d, h, hkv, dh, ffn):
    from paddle_tpu.kernels.decode_megakernel import fused_decode_model
    L, R, pages, pps = 2, 8, 1024, 128
    layers = jax.tree.map(lambda s: _s((L,) + s.shape, s.dtype),
                          _layer_shapes(d, h, hkv, dh, ffn))
    pool = _s((L, hkv, pages, PS, dh))

    def fn(layers, hid, kp, vp, tbl, kl):
        return fused_decode_model(
            layers, hid, kp, vp, tbl, kl, eps=1e-5, theta=10000.0,
            num_heads=h, append_fn=lambda Kp, Vp, k, v: (Kp, Vp))
    return fn, (layers, _s((R, d)), pool, pool, _s((R, pps), jnp.int32),
                _s((R,), jnp.int32)), 1


def _prefill_layer(d, h, hkv, dh, ffn, model_scope=False):
    from paddle_tpu.kernels.prefill_megakernel import (
        fuse_layer_weights, fused_prefill_layer, fused_prefill_model,
        ragged_prologue)
    T, R, pages, pps, qb, L = 128, 8, 1024, 64, 8, 2
    i32 = jnp.int32
    layer, pool = _layer_shapes(d, h, hkv, dh, ffn), _s((hkv, pages, PS, dh))
    if model_scope:
        layer, pool = jax.tree.map(
            lambda s: _s((L,) + s.shape, s.dtype), (layer, pool))

    def fn(layer, hid, kp, vp, pos, tbl, qs, ql, kl):
        pre = ragged_prologue(pos, tbl, qs, ql, theta=10000.0,
                              head_dim=dh, page_size=PS, max_pages=pps,
                              q_block=qb)
        kw = dict(eps=1e-5, num_heads=h, q_block=qb)
        if model_scope:
            fused = jax.vmap(fuse_layer_weights)(layer)
            return fused_prefill_model(fused, hid, kp, vp, tbl, pre, qs,
                                       ql, kl, **kw)
        return fused_prefill_layer(fuse_layer_weights(layer), hid, kp, vp,
                                   tbl, pre, qs, ql, kl, **kw)
    return fn, (layer, _s((1, T, d)), pool, pool, _s((T,), i32),
                _s((R, pps), i32), _s((R,), i32), _s((R,), i32),
                _s((R,), i32)), 1


def _latent(T, row, *, h=128, R=32, pages=16384, pps=1040, append=False):
    """dots-vlm1-inst.long-doc's attention: 128 heads against a latent
    pool of one ``row``-wide row a token, 32 rows x 1,040 page slots;
    with ``append``, the row's append in front, the pool donated."""
    from paddle_tpu.kernels.paged_attention import (kv_append,
                                                    ragged_latent_attention)
    i32 = jnp.int32

    def fn(C, q, x, slot, tbl, qs, ql, kl):
        if append:
            C = kv_append(C[None], slot, x[None])[0]
        return ragged_latent_attention(q, C, tbl, qs, ql, kl, v_width=512,
                                       scale=0.135, q_block=8), C
    return fn, (_s((pages, PS, row)), _s((T, h, row)), _s((T, row)),
                _s((T,), i32), _s((R, pps), i32), _s((R,), i32),
                _s((R,), i32), _s((R,), i32)), 2 if append else 1


CASES = {
    "flash_fwd_d64": lambda: _flash(DH, H, bwd=False),
    "flash_fwd_bwd_d64": lambda: _flash(DH, H, bwd=True),
    "flash_fwd_bwd_d128": lambda: _flash(128, 16, bwd=True),
    "rms_norm_fwd_bwd": _rms_norm,
    "paged_attention_r8": _paged,
    "ragged_t512_qb128_bf16": lambda: _ragged(512, 128, False),
    "ragged_t64_qb8_bf16": lambda: _ragged(64, 8, False),
    "ragged_t512_qb128_int8kv": lambda: _ragged(512, 128, True),
    "ragged_t64_qb8_int8kv": lambda: _ragged(64, 8, True),
    # the geometry the benchmark's serving cell runs (mistral-7b.
    # chat-steady: step_token_budget 320, 32 q / 8 kv heads of 128, 32
    # rows x 128 page slots over a pool of 6,144 pages)
    "ragged_t320_qb8_bf16_mistral7b": lambda: _ragged(
        320, 8, False, h=32, hkv=8, dh=128, R=32, pages=6144),
    # the geometry of k-exaone-236b-a23b.mixed-len: 512 token slots, 64 q
    # / 8 kv heads of 128, 32 rows x 272 page slots; a window layer over
    # the window group's 801 pages, a full layer over 4,096; and the
    # routed experts' up and down projections over 16 held experts
    "ragged_t512_qb8_bf16_kexaone_window": lambda: _ragged(
        512, 8, False, h=64, hkv=8, dh=128, R=32, pages=801, pps=272,
        window=128),
    "ragged_t512_qb8_bf16_kexaone_full": lambda: _ragged(
        512, 8, False, h=64, hkv=8, dh=128, R=32, pages=4096, pps=272),
    # the fp KV append: a head under the 128 lanes (lane-padded by the
    # wrapper), a burst's 8 one-token rows, an f32 pool
    "kv_append_t64_d64_tinyllama": lambda: _append(64),
    "kv_append_t8_d128": lambda: _append(8, hkv=8, dh=128),
    "kv_append_t320_d128_f32": lambda: _append(
        320, hkv=8, dh=128, dtype=jnp.float32),
    # the geometry of dots-vlm1-inst.long-doc: the latent kernel over
    # rows padded to 640 lanes, and the routed experts at hidden 7168
    "latent_t512_qb8_row640_dotsvlm1": lambda: _latent(512, 640),
    "grouped_matmul_dotsvlm1_up": lambda: _grouped(7168, 2048),
    "grouped_matmul_dotsvlm1_down": lambda: _grouped(2048, 7168),
    "grouped_matmul_kexaone_up": lambda: _grouped(6144, 2048),
    "grouped_matmul_kexaone_down": lambda: _grouped(2048, 6144),
    "fused_adamw_f32": lambda: _adamw(11_534_336, jnp.float32),
    "fused_adamw_bf16": lambda: _adamw(11_534_336, jnp.bfloat16),
    "dequant_matmul_int8": lambda: _dequant(8),
    "dequant_matmul_int4": lambda: _dequant(4),
    "fused_decode_layer_r8_small": lambda: _decode_layer(**SMALL),
    "fused_decode_model_r8_small": lambda: _decode_model(**SMALL),
    "fused_prefill_layer_t128_small": lambda: _prefill_layer(**SMALL),
    "fused_decode_layer_r8": lambda: _decode_layer(**TINYLLAMA),
    "fused_decode_model_r8": lambda: _decode_model(**TINYLLAMA),
    "fused_prefill_layer_t128": lambda: _prefill_layer(**TINYLLAMA),
    "fused_prefill_model_t128": lambda: _prefill_layer(
        **TINYLLAMA, model_scope=True),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, on_tpu):
    fn, shapes, n_calls = CASES[name]()
    text = _compiled_text(fn, one_chip, *shapes)
    assert text.count("tpu_custom_call") >= n_calls, (
        f"{name}: expected >= {n_calls} tpu_custom_call in the compiled "
        f"text, found {text.count('tpu_custom_call')}")


# ---- the fp KV append in front of the ragged kernel, pools donated, as
# the serving step's layer body has them (spec_decode._ragged_fp_layer)

def _scatter_append(P, slot, x, interpret=False):
    """The expression ``kv_append`` replaced (PR 30): XLA gives this
    scatter a slot-major operand, so the whole pool is transposed in and
    back out for the kernel behind it."""
    hkv, n, ps, d = P.shape
    return P.reshape(hkv, n * ps, d).at[:, slot].set(x).reshape(P.shape)


def _append_then_ragged(append, T, *, h, hkv, pages, pps, window=None,
                        R=32, dh=128):
    from paddle_tpu.kernels.paged_attention import ragged_paged_attention
    i32 = jnp.int32
    pool = _s((hkv, pages, PS, dh))

    def fn(Kp, Vp, q, kt, vt, slot, tbl, qs, ql, kl):
        Kp, Vp = append(Kp, slot, kt), append(Vp, slot, vt)
        return ragged_paged_attention(q, Kp, Vp, tbl, qs, ql, kl,
                                      q_block=8, window=window), Kp, Vp
    return fn, (pool, pool, _s((T, h, dh)), _s((hkv, T, dh)),
                _s((hkv, T, dh)), _s((T,), i32), _s((R, pps), i32),
                _s((R,), i32), _s((R,), i32), _s((R,), i32))


def _sized_ops(text, op, elems):
    """The compiled text's ``op`` instructions (inside fusions too) whose
    result has at least ``elems`` elements."""
    import math
    import re
    found = []
    for line in text.splitlines():
        m = re.search(r"=\s*\w+\[([\d,]+)\]\S*\s+" + op + r"\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) >= elems:
            found.append(line.strip()[:120])
    return found


def _pool_sized_copies(text, pool_elems):
    return _sized_ops(text, "copy", pool_elems)


# mistral-7b.chat-steady's pools and step, and the window group of
# k-exaone-236b-a23b.mixed-len (801 pages: no multiple of 8)
APPEND_GEOMETRY = {
    "mistral7b": dict(T=320, h=32, hkv=8, pages=6144, pps=128),
    "kexaone_window": dict(T=512, h=64, hkv=8, pages=801, pps=272,
                           window=128),
}


@pytest.mark.parametrize("name", list(APPEND_GEOMETRY))
def test_kv_append_leaves_the_pool_where_it_lies(name, one_chip, on_tpu):
    from paddle_tpu.kernels.paged_attention import kv_append
    geo = APPEND_GEOMETRY[name]
    fn, shapes = _append_then_ragged(kv_append, **geo)
    text = _compiled_text(fn, one_chip, *shapes, donate=(0, 1))
    assert text.count("tpu_custom_call") >= 3
    copies = _pool_sized_copies(text, geo["hkv"] * geo["pages"] * PS * 128)
    assert not copies, f"{name}: pool-sized copies in the step: {copies}"
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    assert "(0, {}" in alias and "(1, {}" in alias, (
        f"{name}: the donated pools are not both aliased to results: "
        f"{alias[:200]}")


def test_latent_append_leaves_the_pool_where_it_lies(one_chip, on_tpu):
    """The latent row's one append a layer, the pool donated: aliased to
    the result through the kv-head axis the append kernel's pages carry
    (a reshape), no pool-sized copy."""
    fn, shapes, _ = _latent(512, 640, append=True)
    text = _compiled_text(fn, one_chip, *shapes, donate=(0,))
    assert text.count("tpu_custom_call") >= 2
    copies = _pool_sized_copies(text, 16384 * PS * 640)
    assert not copies, f"pool-sized copies in the step: {copies}"
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    assert "(0, {}" in alias, alias[:200]


def test_the_chip_holds_a_576_wide_row_in_640_lanes(one_chip, on_tpu):
    """Why the latent pool declares its rows padded (512 + 64 = 576 ->
    640): the chip's compiler lays a 576-wide bf16 pool out as
    ``memref<...x640xbf16>`` (HBM tiling (8, 128)) and refuses the
    kernel's page slice of it, so an unpadded declaration would hold the
    same bytes and not compile."""
    fn, shapes, _ = _latent(512, 576)
    with pytest.raises(Exception, match="aligned to tiling|640"):
        _compiled_text(fn, one_chip, *shapes)


def test_the_scatter_kv_append_replaced_turns_the_pool(one_chip, on_tpu):
    """The guard above can see what it guards against: the old
    expression compiles to two pool-sized copies a pool."""
    geo = APPEND_GEOMETRY["mistral7b"]
    fn, shapes = _append_then_ragged(_scatter_append, **geo)
    text = _compiled_text(fn, one_chip, *shapes, donate=(0, 1))
    copies = _pool_sized_copies(text, geo["hkv"] * geo["pages"] * PS * 128)
    assert len(copies) == 4, copies


# ---- the fused optimizer's bucket update (optimizer/fused.py): leaves
# whose total is no multiple of the kernel's chunk, as every real model's
# (the two [2048] norms break the alignment)
BUCKET_LEAVES = [(1024, 2048), (2048, 1024), (512, 2048), (2048,), (2048,)]


def _engine_bucket():
    """The real engine's one f32 AdamW bucket over BUCKET_LEAVES and its
    jitted update, state donated as ``FusedOptimizerEngine._run`` has it
    (the eager path's: a compiled ``TrainStep`` builds no bucket, see
    ``test_a_train_step_updates_every_leaf_where_it_lies``)."""
    import numpy as np
    import paddle_tpu as paddle
    params = []
    for shape in BUCKET_LEAVES:
        t = paddle.to_tensor(np.zeros(shape, np.float32))
        t.stop_gradient = False
        params.append(t)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4, parameters=params,
                                 weight_decay=0.01)
    assert opt._prime_fused(params)
    bucket, = opt._fused_engine.buckets
    return bucket, opt._fused_engine._bucket_fn(
        bucket, use_scale=False, donate=True, use_mask=False)


def test_the_bucket_update_pads_and_slices_nothing(one_chip, on_tpu):
    """The bucket is laid out once at the kernel's alignment: params and
    grads are concatenated straight to that length, the moments rest at
    it, so the step holds no bucket-sized pad or slice round the one
    kernel call, and the donated moments are updated where they lie."""
    from paddle_tpu.kernels.fused_adamw import BUCKET_ALIGN
    bucket, fn = _engine_bucket()
    assert bucket.total % BUCKET_ALIGN, "the leaves must not align"
    assert bucket.length % BUCKET_ALIGN == 0

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    leaves = tuple(sds(p._data) for p in bucket.params)
    state = {k: sds(v) for k, v in bucket.state.items()}
    assert all(v.shape == (bucket.length,) for v in state.values())
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = fn.lower(leaves, leaves, state, bucket.aux, f32, i32, f32, f32) \
        .compile().as_text()
    assert text.count("tpu_custom_call") == 1
    half = bucket.total // 2
    assert max(bucket.sizes) < half, "a leaf cut is no bucket pass"
    for op in ("pad", "slice"):
        found = _sized_ops(text, op, half)
        assert not found, f"bucket-sized {op} in the update: {found}"
    # flat arguments: the leaves, their grads, then moment1, moment2
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    n = len(leaves)
    assert f"({2 * n}, {{}}" in alias and f"({2 * n + 1}, {{}}" in alias, (
        f"the donated moments are not both aliased to results: "
        f"{alias[:200]}")


@pytest.mark.parametrize("form", ["rows", "flat"])
def test_the_chunked_loss_keeps_each_ranks_rows_on_the_chip(topo, form):
    """The chunked loss and its gradients compiled for a described v5e
    2x2 mesh, hidden states split over ``data``, the tied weight's vocab
    over ``model``: handed ``[batch, seq, hidden]`` the chip's partitioner
    moves nothing but all-reduces; the flat ``[tokens, hidden]`` form it
    replaced (the control) gathers the hidden states onto every chip."""
    import re
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.nn.functional.loss import _fused_linear_cross_entropy
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    b, s, d, v = 4, 255, 512, 2048       # 255 positions: each row pads

    def loss(h, w, lbl):
        if form == "flat":
            h, lbl = h.reshape(-1, d), lbl.reshape(-1)
        return _fused_linear_cross_entropy(
            h, w, lbl, chunk_size=256, transpose_weight=True,
            reduction="mean", ignore_index=-100)

    def spec(shape, dtype, *axes):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*axes)))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        spec((b, s, d), jnp.bfloat16, "data"),
        spec((v, d), jnp.bfloat16, "model"),
        spec((b, s), jnp.int32, "data")).compile().as_text()
    moved = re.findall(r"= \S+ (all-gather|all-to-all)(?:-start)?\(", text)
    assert "all-reduce" in text
    assert bool(moved) == (form == "flat"), moved


def test_an_unaligned_fused_adamw_pads_and_slices_the_bucket(one_chip,
                                                             on_tpu):
    """The guard above can see what it guards against: handed the same
    leaves' total unpadded, as the engine handed it before the bucket
    carried its own length, the kernel's wrapper pads p, g, m, v and
    slices its three results, each a pass over the whole bucket."""
    import math
    n = sum(math.prod(shape) for shape in BUCKET_LEAVES)
    fn, shapes, _ = _adamw(n, jnp.float32)
    text = _compiled_text(fn, one_chip, *shapes)
    assert len(_sized_ops(text, "pad", n // 2)) == 4
    assert len(_sized_ops(text, "slice", n // 2)) == 3


def test_a_train_step_updates_every_leaf_where_it_lies(one_chip,
                                                       monkeypatch):
    """ISSUE 40. A two-layer step of SmolLM2's shapes at a quarter of
    its widths (tests/trainstep_witness.py), built as the training cells
    build theirs and compiled whole for the chip: under
    ``phase.optimizer`` no concatenation, no ``dynamic-update-slice``,
    no ``fused_adamw`` kernel and no result as large as the leaves
    together, and every parameter and moment input is aliased to a
    result (updated in place)."""
    import re
    from paddle_tpu.profiler import phases
    import paddle_tpu.kernels as K
    from trainstep_witness import (flat_bucket_traces,
                                   optimizer_instructions,
                                   smollm2_like_step)
    step, model, opt, ids = smollm2_like_step(batch=2)
    seen = []
    real = phases.launch_specs
    monkeypatch.setattr(phases, "launch_specs",
                        lambda args: seen.append(real(args)) or seen[-1])
    step(ids)                       # on this host: builds the specialization
    specs, = seen
    jitted, = step._cache.values()
    # the same traced function, donated as on the chip, the kernels'
    # entry points steered onto their TPU branch
    monkeypatch.setattr(K, "_ON_TPU", True)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS", raising=False)
    specs = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
        if isinstance(s, jax.ShapeDtypeStruct) else s, specs)
    text = jax.jit(jitted.__wrapped__, donate_argnums=(0, 1)) \
        .lower(*specs).compile().as_text()
    assert optimizer_instructions(text), "no phase.optimizer in the text"
    leaves = sum(p._data.size for p in step._params.values())
    assert not flat_bucket_traces(text, leaves)
    assert "fused_adamw" not in text
    # flat arguments: the parameters (a dict: sorted keys), then the state
    n = len(step._params) + len(step._opt_state_arrays())
    alias = text[text.index("input_output_alias="):].split("\n")[0]
    aliased = {int(i) for i in re.findall(r"\((\d+), \{\}", alias)}
    assert set(range(n)) <= aliased, sorted(set(range(n)) - aliased)


# the serving launch's control buffer (spec_decode.StepOperands) at the
# two serving cells' shapes, and with every optional operand aboard
PROLOGUE_GEOMETRY = {
    "mistral7b": dict(T=320, R=32, PPS=128, K=0),
    "kexaone_window": dict(T=512, R=32, PPS=272, K=0, window=True),
    "spec_lora_window": dict(T=320, R=32, PPS=128, K=2, window=True,
                             adapters=True),
}


@pytest.mark.parametrize("name", list(PROLOGUE_GEOMETRY))
def test_step_prologue_unpacks_the_one_buffer_in_place(name, one_chip):
    """The ragged step's first lines: the operands out of the ONE int32
    control buffer by static slices, the floats by their own bits, and
    the step's small results back as one array. Compiled for the chip,
    the prologue holds no transfer and no copy larger than the buffer
    (slices and bitcasts of it, which XLA fuses into their readers)."""
    from paddle_tpu.serving.spec_decode import StepOperands, _ragged_packing
    geo = dict(PROLOGUE_GEOMETRY[name])
    T, R, K = geo["T"], geo["R"], geo["K"]
    lay = StepOperands(geo.pop("T"), geo.pop("R"), geo.pop("PPS"),
                       geo.pop("K"), **geo)

    def fn(ctl):
        o = lay.unpack(ctl)
        # readers as the step's: a per-token value by token id, the row
        # masks, the sampler's floats
        tok_row, live = _ragged_packing(o["q_starts"], o["q_lens"], T)
        h = jnp.sin(o["tokens"][:, None].astype(jnp.float32)
                    * jnp.arange(1, 9, dtype=jnp.float32)) * live[:, None]
        scaled = h[o["sample_idx"].reshape(-1)].reshape(R, K + 1, 8) \
            / jnp.maximum(o["temps"], 1e-6)[:, None, None]
        finite = jnp.all(jnp.isfinite(scaled.reshape(R, -1)), -1) \
            & (o["top_ps"] <= 1.0)
        out = jnp.argmax(scaled, -1).astype(jnp.int32) + tok_row[:R, None]
        rest = {k: v for k, v in o.items()
                if k not in ("tokens", "temps", "top_ps")}
        return lay.pack_results(out, o["spec_lens"] + 1, finite), rest

    text = _compiled_text(fn, one_chip, _s((lay.size,), jnp.int32))
    assert "bitcast-convert" in text or "bitcast(" in text
    for op in ("infeed", "outfeed", "send(", "recv(", "all-gather",
               "all-reduce", "host-compute"):
        assert op not in text, f"{name}: {op} in the step's prologue"
    # the buffer itself may be staged whole (a copy-start of its size)
    import math
    import re
    big = _pool_sized_copies(text, lay.size + 1) + [
        m.group(0)[:120] for m in re.finditer(
            r"\(\w+\[([\d,]+)\]\S* [^\n]*copy-start\(", text)
        if math.prod(map(int, m.group(1).split(","))) > lay.size]
    assert not big, f"{name}: copies larger than the buffer: {big}"


# the serving step's epilogue at mistral-7b.chat-steady's shapes: the
# non-finite guard, then the sampler over 32 rows of a 32,768 vocabulary
EPILOGUE_R, EPILOGUE_V = 32, 32768


def _epilogue_text(sampler, one_chip, K=0):
    """``engine.py::ragged_step``'s last lines round ``sampler``
    (``speculative_sample``'s signature), compiled for the chip."""
    R, V = EPILOGUE_R, EPILOGUE_V
    i32, f32 = jnp.int32, jnp.float32

    def epilogue(logits, *rest):
        finite = jnp.all(jnp.isfinite(logits.reshape(R, -1)), axis=-1)
        return sampler(logits, *rest), finite

    key = jax.eval_shape(lambda: jax.random.key(0))
    return _compiled_text(
        epilogue, one_chip, _s((R, K + 1, V)), _s((R, K), i32),
        _s((R, K, V), f32), _s((R,), i32), _s((R,), f32), _s((R,), i32),
        _s((R,), f32), key, _s((R,), i32), _s((R,), i32))


def _straight_line(text):
    """The lines of the compiled text that run on every call: the entry
    computation's, and those of every computation it reaches (fusions,
    calls, loop bodies, comparators) except through a ``conditional``'s
    branches."""
    import re
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    seen, todo, lines = set(), ["ENTRY"], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            lines.append(line)
            unconditional = re.sub(
                r"(branch_computations=\{[^}]*\}"
                r"|(true|false)_computation=%?[\w.\-]+)", "", line)
            todo += re.findall(r"=\{?%([\w.\-]+)", unconditional)
    return lines


def _vocabulary_sorts(lines):
    import math
    import re
    found = []
    for line in lines:
        m = re.search(r"=\s*\(?\w+\[([\d,]+)\][^=]*\ssort\(", line)
        if m and math.prod(map(int, m.group(1).split(","))) \
                >= EPILOGUE_R * EPILOGUE_V:
            found.append(line.strip()[:120])
    return found


def test_the_sampling_epilogue_sorts_only_inside_a_branch(one_chip):
    """The step's sampler is gated on the rows' knobs by ``cond``s the
    chip's compiler keeps: the compiled text holds ``conditional``s, the
    entry's own among them, and both whole-vocabulary sorts sit inside
    branch computations, none on the path every step runs. Had a
    predicate been batched into a select, its sort would be on it."""
    from paddle_tpu.serving.spec_decode import speculative_sample
    text = _epilogue_text(speculative_sample, one_chip)
    straight = _straight_line(text)
    assert any(" conditional(" in line for line in straight)
    assert len(_vocabulary_sorts(text.splitlines())) == 2
    found = _vocabulary_sorts(straight)
    assert not found, f"a vocabulary sort every step runs: {found}"


def test_the_ungated_epilogue_sorts_on_every_step(one_chip):
    """The guard above can see what it guards against: the sampler as it
    stood before the gate (``tests/sampler_oracle.py``) compiles to two
    whole-vocabulary sorts in the entry's straight line and no
    ``conditional``."""
    import sampler_oracle
    text = _epilogue_text(sampler_oracle.speculative_sample, one_chip)
    assert " conditional(" not in text
    assert len(_vocabulary_sorts(_straight_line(text))) == 2


def test_the_chips_compiler_keeps_the_phases_on_the_ragged_step(one_chip,
                                                                on_tpu):
    """Device time by phase stands on the optimised HLO's metadata
    (``profiler/phases.py``): the ragged step at Mistral-7B's widths
    (one layer of it, hidden 4096, 32 q / 8 kv heads of 128, ffn 14336),
    compiled for the chip, names a phase on its ``fusion`` and ``copy``
    instructions (at least 95 % of them at the cell's depth; what the
    compiler left without metadata is charged to its reader), and
    charges its kernels where
    the trace's families are read (``ragged_paged_attention`` and
    ``kv_append`` to ``attn.core``)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.hlo_forensics import instruction_metadata
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.profiler import phases
    from paddle_tpu.serving import LLMEngine
    paddle.seed(3)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=4096, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=1, num_attention_heads=32, num_key_value_heads=8,
        max_position_embeddings=2048, rope_theta=1e6,
        tie_word_embeddings=False)).bfloat16().eval()
    eng = LLMEngine(model, max_len=2048, max_num_seqs=32, page_size=16,
                    num_pages=256)
    del model
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._zero_step_args())
    text = eng._ragged_jit.lower(*specs).compile().as_text()
    table, _, placed = phases.parse(text)
    xla = [(n, o) for n, op, o, _ in instruction_metadata(text)
           if op in ("fusion", "copy")]
    bare = [(n, o) for n, o in xla if table[n][0] is None]
    # those the first-reader rule placed are the compiler's own (no
    # traced op's metadata), and the table keeps them apart
    by_rule = [(n, o) for n, o in xla if n in placed]
    print("placed by the first-reader rule:", len(by_rule), "of", len(xla))
    assert all(o is None or "/" not in o for _, o in by_rule), by_rule
    assert len(by_rule) <= 0.25 * len(xla), (len(by_rule), len(xla))
    in_layer = sum(table[n][0] in ("norm", "attn.qkv", "attn.core",
                                   "attn.out", "mlp") for n, _ in xla)
    assert in_layer > 40
    # what no phase owns is the step's own prologue and epilogue (the
    # control buffer unpacked, the packing's searchsorted, the results
    # packed): it carries metadata, which names no phase, and does not
    # grow with depth. At the cell's 12 layers it is under 5 % by count
    assert all(o and "phase." not in o for _, o in bare), bare
    assert len(bare) <= 16, bare
    assert len(bare) <= 0.05 * (len(xla) + 11 * in_layer), (len(bare),
                                                            len(xla))
    kernels = {n: table[n][0] for n in table
               if "ragged_paged_attention" in n or "kv_append" in n}
    assert kernels and set(kernels.values()) == {"attn.core"}, kernels
    assert {"embed", "norm", "attn.qkv", "attn.core", "attn.out", "mlp",
            "head", "guard", "sample"} <= {p for p, _ in table.values()}
