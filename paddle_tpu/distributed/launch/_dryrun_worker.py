"""Minimal multi-process dryrun worker (spawned by ``dryrun_multichip``).

Each of the 2 launched processes owns DRYRUN_LOCAL_DEVICES virtual CPU
devices; jax.distributed stitches them into ONE global mesh and a compiled
GSPMD train step (forward + backward + AdamW, dp axis spanning the process
boundary) executes across it. Proves the mesh construction, global-array
placement, and fused-step compilation survive ``process_count > 1``
(reference backbone shape: process_group_nccl.cc:267).
"""
import json
import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ.get("DRYRUN_LOCAL_DEVICES", "4"))
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax

if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")


def main():
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import Replicate, Shard
    from paddle_tpu.distributed.api import shard_parameter, shard_tensor

    dist.init_parallel_env()
    world = dist.get_world_size()
    n = len(jax.devices())
    mesh = dist.init_mesh({"dp": world, "mp": n // world})
    paddle.seed(0)
    model = paddle.nn.Linear(8, 8)
    mp_i = mesh.dim_names.index("mp")
    shard_parameter(model.weight, mesh,
                    [Shard(1) if i == mp_i else Replicate()
                     for i in range(mesh.ndim)])
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model,
        lambda xb, yb: paddle.nn.functional.mse_loss(model(xb), yb), opt)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4 * world, 8)).astype(np.float32)
    dp_pl = [Shard(0) if i != mp_i else Replicate()
             for i in range(mesh.ndim)]
    xt = shard_tensor(paddle.to_tensor(x), mesh, dp_pl)
    yt = shard_tensor(paddle.to_tensor(x @ np.eye(8, dtype=np.float32)),
                      mesh, dp_pl)
    losses = [float(step(xt, yt).numpy()) for _ in range(2)]
    assert all(np.isfinite(l) for l in losses), losses
    if dist.get_rank() == 0:
        with open(os.environ["DRYRUN_MP_OUT"], "w") as f:
            json.dump({"losses": losses, "devices": n}, f)


if __name__ == "__main__":
    main()
