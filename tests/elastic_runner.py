"""Worker script: elastic restart + checkpoint-resume end to end.

Spawned by the launch CLI with --max_restart >= 1. Incarnation 1 of rank
1 CRASHES mid-training (after step 3); the controller restarts the pod;
incarnation 2 resumes from the per-step checkpoint and finishes. The
parent test asserts the full trajectory matches an uninterrupted run —
the reference's elastic manager contract (fleet/elastic/manager.py:125:
detect failure, restart workers, training resumes from state).
"""
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

TOTAL_STEPS = 6
CRASH_AFTER = 3


def main():
    dist.init_parallel_env()
    rank, world = dist.get_rank(), dist.get_world_size()
    workdir = os.environ["ELASTIC_DIR"]
    ckpt = os.path.join(workdir, f"ckpt_rank{rank}.npz")
    marker = os.path.join(workdir, f"crashed_rank{rank}")

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 4)).astype(np.float32)
    w_true = np.arange(4, dtype=np.float32).reshape(4, 1)
    y = x @ w_true
    shard = 8 // world
    xs = paddle.to_tensor(x[rank * shard:(rank + 1) * shard])
    ys = paddle.to_tensor(y[rank * shard:(rank + 1) * shard])

    lin = paddle.nn.Linear(4, 1)
    lin.weight._data = jax.numpy.zeros((4, 1))
    lin.bias._data = jax.numpy.zeros((1,))
    opt = paddle.optimizer.SGD(parameters=lin.parameters(),
                               learning_rate=0.1)
    start = 0
    if os.path.exists(ckpt):          # resume after the elastic restart
        data = np.load(ckpt)
        lin.weight._data = jax.numpy.asarray(data["w"])
        lin.bias._data = jax.numpy.asarray(data["b"])
        start = int(data["step"])

    losses = []
    for step in range(start, TOTAL_STEPS):
        loss = paddle.nn.functional.mse_loss(lin(xs), ys)
        loss.backward()
        for p in lin.parameters():
            if p.grad is not None:
                dist.all_reduce(p.grad, op=ReduceOp.AVG)
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
        np.savez(ckpt, w=np.asarray(lin.weight.numpy()),
                 b=np.asarray(lin.bias.numpy()), step=step + 1)
        if rank == 1 and step + 1 == CRASH_AFTER \
                and not os.path.exists(marker):
            open(marker, "w").write("1")
            os._exit(17)              # simulated hard failure

    if rank == 0:
        out = {
            "resumed_from": start,
            "final_w": np.asarray(lin.weight.numpy()).ravel().tolist(),
            "final_b": np.asarray(lin.bias.numpy()).ravel().tolist(),
            "losses": losses,
        }
        # both incarnations of rank 0 write; the LAST (resumed) one wins
        with open(os.path.join(workdir, "result.json"), "w") as f:
            json.dump(out, f)


if __name__ == "__main__":
    main()
