"""Worker script: COMPILED SPMD programs across real processes.

Spawned by the launch CLI (2 processes x 4 local CPU devices = one global
8-device mesh through jax.distributed). Round-2 verdict item #1: every
compiled distributed program had only ever run single-controller; this
runner executes them across a genuine process boundary (the reference's
backbone shape — one process per host, process_group_nccl.cc:267; the
end-to-end pattern test/legacy_test/test_dist_base.py):

  [A] GSPMD dp x mp fused TrainStep — dp axis SPANS the two processes, so
      the gradient all-reduce crosses the boundary. 20 steps; rank 0
      records the loss curve + final (gathered) params for parity with a
      single-process run in the parent test.
  [B] generic hybrid pipeline step (build_hybrid_step) on a pp x dp mesh —
      the pp axis spans the processes, so ppermute activation hops cross
      the boundary. Records loss + grad-finiteness.
  [C] sharded distributed checkpoint: save the mp-sharded params from [A]
      (every process writes only its addressable shards), reload under a
      DIFFERENT mesh layout (reshard-on-load across the process boundary),
      assert exact roundtrip.
"""
import json
import os

if __name__ == "__main__":  # worker process: 4 local devices of the 8
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import jax
import jax.numpy as jnp

if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_matmul_precision", "highest")

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.distributed as dist  # noqa: E402
from paddle_tpu.distributed import Replicate, Shard  # noqa: E402
from paddle_tpu.distributed.api import shard_parameter, shard_tensor  # noqa: E402


class MLP(paddle.nn.Layer):
    """Megatron-style 2-layer MLP: fc1 column-parallel, fc2 row-parallel."""

    def __init__(self):
        super().__init__()
        self.fc1 = paddle.nn.Linear(16, 32)
        self.fc2 = paddle.nn.Linear(32, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def build_and_train(mesh, n_steps=20):
    """The [A] program. Deterministic given paddle.seed — the parent test
    re-runs it single-process for parity."""
    paddle.seed(0)
    model = MLP()
    rep = [Replicate()] * mesh.ndim
    mp_i = mesh.dim_names.index("mp")
    col = list(rep); col[mp_i] = Shard(1)      # fc1 W [in, out]: split out
    row = list(rep); row[mp_i] = Shard(0)      # fc2 W [in, out]: split in
    shard_parameter(model.fc1.weight, mesh, col)
    shard_parameter(model.fc1.bias, mesh,
                    [Shard(0) if i == mp_i else Replicate()
                     for i in range(mesh.ndim)])
    shard_parameter(model.fc2.weight, mesh, row)
    shard_parameter(model.fc2.bias, mesh, rep)

    rng = np.random.default_rng(0)
    x = rng.standard_normal((16, 16)).astype(np.float32)
    w_true = rng.standard_normal((16, 4)).astype(np.float32)
    y = x @ w_true

    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model,
        lambda xb, yb: paddle.nn.functional.mse_loss(model(xb), yb),
        opt)

    dp_pl = [Shard(0) if n == "dp" else Replicate() for n in mesh.dim_names]
    xt = shard_tensor(paddle.to_tensor(x), mesh, dp_pl)
    yt = shard_tensor(paddle.to_tensor(y), mesh, dp_pl)
    losses = [float(step(xt, yt).numpy()) for _ in range(n_steps)]
    return model, losses


def main():
    dist.init_parallel_env()
    rank, world = dist.get_rank(), dist.get_world_size()
    assert world == 2, f"runner expects 2 processes, got {world}"
    assert len(jax.devices()) == 8, (
        f"expected an 8-device global mesh, got {len(jax.devices())}")
    result = {"n_global_devices": len(jax.devices())}

    # ---- [A] dp(2, across processes) x mp(4) fused TrainStep ----
    mesh = dist.init_mesh({"dp": 2, "mp": 4})
    model, losses = build_and_train(mesh)
    result["A_losses"] = losses
    # gather final params for the parity check (replicated-readable)
    final = {}
    for name, p in model.named_parameters():
        rep = shard_tensor(p, mesh, [Replicate()] * mesh.ndim)
        final[name] = np.asarray(rep.numpy()).tolist()
    result["A_params"] = final

    # ---- [B] pipeline across the process boundary: pp(2) x dp(4) ----
    from paddle_tpu.distributed.hybrid_parallel import build_hybrid_step
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh2 = dist.init_mesh({"pp": 2, "dp": 4})
    paddle.seed(3)
    dmodel = 8

    class Block(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = paddle.nn.Linear(dmodel, dmodel)

        def forward(self, x):
            return x + paddle.tanh(self.fc(x))

    blocks = [Block() for _ in range(4)]
    gp, gstep = build_hybrid_step(
        blocks, lambda yy, ll: jnp.mean((yy - ll) ** 2), mesh2,
        n_micro=2, schedule="1f1b")
    # place stacked block params on the pp axis (global arrays)
    jm = mesh2.jax_mesh
    gp = {"blocks": jax.tree.map(
        lambda l: jax.make_array_from_callback(
            l.shape, NamedSharding(jm, P("pp")),
            lambda idx, l=l: np.ascontiguousarray(np.asarray(l)[idx])),
        gp["blocks"])}
    xb_np = np.random.default_rng(4).standard_normal(
        (8, 4, dmodel)).astype(np.float32)
    xb = jax.make_array_from_callback(
        xb_np.shape, NamedSharding(jm, P()), lambda idx: xb_np[idx])
    gl, ggrads = jax.jit(gstep)(gp, xb, jnp.zeros_like(xb))
    result["B_loss"] = float(gl)
    result["B_grads_finite"] = all(
        bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(ggrads))

    # ---- [C] sharded checkpoint save + reshard-on-load ----
    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict)

    ckpt_dir = os.environ["SPMD_CKPT_DIR"]
    state = {n: p for n, p in model.named_parameters()}
    save_state_dict(state, ckpt_dir)
    dist.barrier()
    # destination: a different layout — mp degree 2 on the FIRST axis,
    # dp 4 on the second; every tensor re-places across the boundary
    mesh3 = dist.init_mesh({"mp": 2, "dp": 4})
    paddle.seed(1)
    dest = MLP()
    mp_i = mesh3.dim_names.index("mp")
    dst_state = {n: p for n, p in dest.named_parameters()}
    shard_parameter(dest.fc1.weight, mesh3,
                    [Shard(1) if i == mp_i else Replicate()
                     for i in range(mesh3.ndim)])
    load_state_dict(dst_state, ckpt_dir)
    ok = True
    for n, p in dest.named_parameters():
        rep = shard_tensor(p, mesh3, [Replicate()] * mesh3.ndim)
        ok = ok and bool(np.allclose(np.asarray(rep.numpy()),
                                     np.asarray(result["A_params"][n])))
    result["C_roundtrip_ok"] = ok

    # ---- [D] cross-mesh reshard across the process boundary ----
    # live-tensor analog of [C]: an mp-sharded GLOBAL tensor moves onto a
    # sub-mesh owned entirely by process 0, then back onto a permuted
    # global mesh (reference: same_status / global<->sub-mesh reshard)
    from paddle_tpu.distributed.mesh import ProcessMesh

    devs = [d.id for d in jax.devices()]
    mesh_g = dist.init_mesh({"dp": 2, "mp": 4})
    sub = ProcessMesh(np.asarray(devs[:4]), ["mp"])     # process 0 only
    perm = ProcessMesh(np.asarray(devs[::-1]).reshape(4, 2), ["mp", "dp"])
    val = np.arange(32, dtype=np.float32).reshape(8, 4)
    tg = shard_tensor(paddle.to_tensor(val), mesh_g,
                      [Shard(0), Shard(1)])
    ts = dist.reshard(tg, sub, [Shard(0)])
    ok_d = True
    if rank == 0:   # only process 0 can read the sub-mesh tensor
        ok_d = bool(np.array_equal(np.asarray(ts.numpy()), val))
    tb = dist.reshard(ts, perm, [Shard(1), Replicate()])
    ok_d = ok_d and bool(np.array_equal(np.asarray(
        dist.reshard(tb, mesh_g, [Replicate(), Replicate()]).numpy()), val))
    result["D_cross_mesh_ok"] = ok_d

    dist.barrier()
    if rank == 0:
        with open(os.environ["SPMD_OUT"], "w") as f:
            json.dump(result, f)


if __name__ == "__main__":
    main()
