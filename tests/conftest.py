"""Test configuration: run everything on an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): "multi-node" testing is
multi-device single-host; the XLA-CPU 8-device stand-in plays the role the
reference gives loopback NCCL.
"""
import os

# Tests are CPU-only: JAX is held to the CPU before it is imported, which
# also covers the worker subprocesses that tests spawn (launch CLI tests
# re-exec python and inherit the environment).
os.environ["JAX_PLATFORMS"] = "cpu"

# XLA parses XLA_FLAGS at backend-creation time, and backends are created
# lazily, so setting it here lands before any device exists.
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402

# and by config too, should a pytest plugin have imported jax before this
# file ran (the variable is read when jax is imported)
jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Numeric tests compare against float32 numpy; the default matmul precision on
# this stack is TPU-like (bf16 passes), so pin highest precision for testing.
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache: repeated suite runs skip recompiles (the
# analog of the reference's build-cache CI tier, tools/parallel_UT_rule.py).
# Placed from outside by JAX_COMPILATION_CACHE_DIR (JAX reads it itself);
# else the test tier's own fixed path.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      "/tmp/paddle_tpu_jax_test_cache")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield
