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
