"""Worker script: data-parallel convergence across real processes (spawned
by the launch CLI). Each rank trains on its half of a fixed batch, averaging
gradients with the eager all_reduce; rank 0 writes final loss + params so
the parent test can assert parity with a single-process run on the full
batch (the reference pattern: test/legacy_test/test_dist_base.py)."""
import json
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.distributed.collective import ReduceOp  # noqa: E402


def main():
    dist.init_parallel_env()
    rank, world = dist.get_rank(), dist.get_world_size()
    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 4)).astype(np.float32)
    w_true = np.arange(4, dtype=np.float32).reshape(4, 1)
    y = x @ w_true

    shard = 16 // world
    xs = paddle.to_tensor(x[rank * shard:(rank + 1) * shard])
    ys = paddle.to_tensor(y[rank * shard:(rank + 1) * shard])

    lin = paddle.nn.Linear(4, 1)
    # identical init on every rank (the DataParallel broadcast contract)
    lin.weight._data = jax.numpy.zeros((4, 1))
    lin.bias._data = jax.numpy.zeros((1,))
    opt = paddle.optimizer.SGD(parameters=lin.parameters(), learning_rate=0.1)

    loss_val = None
    for _ in range(40):
        loss = paddle.nn.functional.mse_loss(lin(xs), ys)
        loss.backward()
        for p in lin.parameters():
            if p.grad is not None:
                dist.all_reduce(p.grad, op=ReduceOp.AVG)
        opt.step()
        opt.clear_grad()
        loss_val = float(loss.numpy())

    # global loss for parity: average of per-rank losses
    t = paddle.to_tensor(np.asarray([loss_val], np.float32))
    dist.all_reduce(t, op=ReduceOp.AVG)
    if rank == 0:
        out = {
            "loss": float(t.numpy()[0]),
            "w": np.asarray(lin.weight.numpy()).ravel().tolist(),
            "b": np.asarray(lin.bias.numpy()).ravel().tolist(),
        }
        with open(os.environ["DP_OUT"], "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main()
