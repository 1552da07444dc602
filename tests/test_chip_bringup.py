"""What the chip bring-up (PR 22) changed, pinned on the CPU tier: no
fallback that hides the device, the compile cache placed from outside,
the chip's HLO text read right, and chip_smoke.py's refusal without an
accelerator. The kernels' compiles for a described v5e are
tests/test_tpu_compile.py; the legs themselves run on the chip only.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_without_an_accelerator():
    """Under JAX_PLATFORMS=cpu the script exits non-zero and prints no
    result line (one subprocess: it must own its JAX)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stderr


def test_chip_smoke_reads_kernels_from_compiled_text():
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    text = "\n".join([
        '  %ragged_paged_attention.3 = bf16[128,4,8,64]{3,2,1,0:T(8,128)'
        '(2,1)} custom-call(%a, %b), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(ragged_step)/ragged_paged_attention/'
        'pallas_call" stack_frame_id=9}',
        '  %fused_adamw.1 = (f32[8,128]{1,0:T(8,128)}) custom-call(%p), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(pure_step)/shard_map/fused_adamw/pallas_call"}',
        '  %x = f32[8]{0} custom-call(%y), custom_call_target="Sharding"',
        '  %ragged_paged_attention.4 = bf16[8]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(ragged_step)/ragged_paged_attention/pallas_call"}'])
    assert chip_smoke.kernels_in_hlo(text) == {
        "ragged_paged_attention": 2, "fused_adamw": 1}


@pytest.mark.parametrize("case", ["no_accelerator", "index_past_the_end",
                                  "cpu_tier_folds"])
def test_tpu_place_names_a_real_device(case, monkeypatch):
    """A ``tpu:N`` place resolves to host devices only where JAX_PLATFORMS
    asks for the CPU (the test tier); elsewhere an index that names no
    accelerator is an error, never folded onto what exists."""
    from paddle_tpu.core import place
    host = jax.devices()
    if case == "cpu_tier_folds":
        assert place.TPUPlace(len(host) + 3).jax_device() in host
        return
    monkeypatch.setattr(place, "_cpu_requested", lambda: False)
    accel = [] if case == "no_accelerator" else host[:1]
    monkeypatch.setattr(place, "_accelerators", lambda: accel)
    with pytest.raises(RuntimeError, match="names no device"):
        place.TPUPlace(len(accel)).jax_device()
    if accel:
        assert place.TPUPlace(0).jax_device() is accel[0]


def test_backend_probe_lets_a_backend_failure_out(monkeypatch):
    """``_on_tpu()`` used to read ANY exception from jax.devices() as "not
    on TPU", after which every kernel took its jnp or interpret branch."""
    import paddle_tpu.kernels as K

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(K, "_ON_TPU", None)
    monkeypatch.setattr(K.jax, "devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        K._on_tpu()
    assert K._ON_TPU is None          # a failure is not cached as an answer


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_is_placed_from_outside(placed, monkeypatch,
                                              tmp_path):
    from paddle_tpu.core import compile_cache as cc
    saved = jax.config.jax_compilation_cache_dir
    try:
        if placed:
            monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
            assert cc.enable_compile_cache() == str(tmp_path)
            # nothing in code touched the config
            assert jax.config.jax_compilation_cache_dir == saved
            cc.disable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == saved
        else:
            monkeypatch.delenv(cc.ENV_VAR, raising=False)
            got = cc.enable_compile_cache()
            assert got == cc.DEFAULT_DIR == \
                jax.config.jax_compilation_cache_dir
            assert os.path.dirname(got) == REPO       # inside the checkout
            assert not got.startswith("/tmp")
            with open(os.path.join(REPO, ".gitignore")) as f:
                assert os.path.basename(got) + "/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


def test_collective_counts_read_tpu_layouts():
    """The chip's HLO text writes layouts with parentheses of their own
    (``{0:T(8,128)S(1)}``) and tuple result types with spaces; an operand
    reference (``%all-reduce.3``) is not an op."""
    from paddle_tpu.distributed.gspmd import (collective_counts,
                                              pipeline_permute_counts)
    text = "\n".join([
        "  %all-reduce.60 = f32[512]{0:T(512)S(1)} all-reduce(%gte.4), "
        "channel_id=3, replica_groups={{0,1},{2,3}}",
        "  %all-reduce.61 = (f32[512]{0:T(512)S(1)}, f32[512]{0:T(512)"
        "S(1)}) all-reduce(%b.9, %f.2), channel_id=4",
        "  %ag-start = bf16[8,512]{1,0:T(8,128)(2,1)S(1)} "
        "all-gather-start(%gte.5), channel_id=2",
        "  %ag-done = bf16[8,512]{1,0:T(8,128)(2,1)} "
        "all-gather-done(%ag-start)",
        "  %add.7 = f32[512]{0:T(512)} add(%all-reduce.60, %all-reduce.61)",
        "  %cp.1 = f32[8]{0:T(8)} collective-permute(%x), channel_id=9, "
        "source_target_pairs={{0,1},{1,0}}",
        "  %ar.cpu = f32[8]{0} all-reduce(f32[8]{0} %y), to_apply=%sum"])
    assert collective_counts(text) == {
        "all_reduce": 3, "all_gather": 1, "reduce_scatter": 0,
        "collective_permute": 1, "all_to_all": 0}
    assert pipeline_permute_counts(text, pipe=2) == {
        "ring": 1, "other": 0, "total": 1}


def test_fused_adamw_is_a_manual_region_under_a_mesh(monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically: under an
    active GSPMD mesh the fused AdamW kernel runs inside shard_map (the
    flat bucket is replicated there), and steps aside for the
    partitioner's own elementwise update when ZeRO shards the state."""
    from jax.sharding import Mesh
    from paddle_tpu.distributed import gspmd
    from paddle_tpu.kernels.fused_adamw import _reference, maybe_fused_adamw
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS", "1")   # interpreter
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                (gspmd.DATA_AXIS, gspmd.MODEL_AXIS))
    rng = np.random.default_rng(0)
    n = 512 * 128
    p, g = (jnp.asarray(rng.standard_normal(n), jnp.float32) for _ in "pg")
    m = v = jnp.zeros(n, jnp.float32)
    kw = dict(beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01,
              decoupled=True)

    def step(p, g, m, v):
        return maybe_fused_adamw(p, g, m, v, 1e-3, 1, **kw)

    with gspmd.partitioning_scope(mesh):
        jitted = jax.jit(step)
        assert "shard_map" in str(jax.make_jaxpr(step)(p, g, m, v))
        got = jitted(p, g, m, v)
    want = _reference(p, g, m, v, 1e-3, 1 - 0.9, 1 - 0.999, beta1=0.9,
                      beta2=0.999, eps=1e-8, wd=0.01, decoupled=True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    dp_only = Mesh(np.asarray(jax.devices()[:2]), (gspmd.DATA_AXIS,))
    with gspmd.partitioning_scope(dp_only, zero=True):
        assert gspmd.flat_state_sharded()
        assert maybe_fused_adamw(p, g, m, v, 1e-3, 1, **kw) is None
    with gspmd.partitioning_scope(mesh, zero=True):
        assert not gspmd.flat_state_sharded()    # zero x tp: replicated
