"""Module-level worker for paddle.distributed.spawn tests (spawn pickles
the function, so it must live in an importable module)."""
import os


def allreduce_worker(out_dir):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    rank, world = dist.get_rank(), dist.get_world_size()
    t = paddle.to_tensor(np.full((4,), float(rank + 1), np.float32))
    dist.all_reduce(t)
    dist.wait(t)
    expected = sum(range(1, world + 1))
    assert np.allclose(t.numpy(), expected), (t.numpy(), expected)
    with open(os.path.join(out_dir, f"rank{rank}.ok"), "w") as f:
        f.write(str(world))


def failing_worker():
    raise RuntimeError("deliberate failure")
