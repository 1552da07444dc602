"""Sharded-optimizer / ZeRO stages.

TPU-native analog of the reference's group_sharded stack (reference:
python/paddle/distributed/sharding/group_sharded.py:50
group_sharded_parallel; stage1 DygraphShardingOptimizer
fleet/meta_optimizers/dygraph_optimizer/dygraph_sharding_optimizer.py:54;
stage2 group_sharded_optimizer_stage2.py:53; stage3
group_sharded_stage3.py:85). The reference manually slices params/grads/
states per rank and broadcasts/allgathers around optimizer.step(). Here each
stage is a *sharding declaration* over the 'sharding' (or 'dp') mesh axis:

- stage 1 ("os"): optimizer states sharded on dim 0;
- stage 2 ("os_g"): + gradients sharded as they accumulate;
- stage 3 ("p_g_os"): + parameters sharded — GSPMD all-gathers a param
  exactly where its value is consumed (the reference's _all_gather-on-use,
  group_sharded_stage3.py:60) and frees the gathered copy after use, which
  is XLA's buffer liveness doing the reference's release_param bookkeeping.
"""
from __future__ import annotations

import jax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from .mesh import ProcessMesh
from .placement import Replicate, Shard


def _axis_placements(mesh: ProcessMesh, axis_name: str, tensor_dim=0):
    pl = [Replicate()] * mesh.ndim
    if axis_name in mesh.dim_names:
        pl[mesh.dim_names.index(axis_name)] = Shard(tensor_dim)
    return pl


def _shardable(arr, degree):
    return arr.ndim >= 1 and arr.shape[0] % degree == 0 and arr.shape[0] >= degree


def shard_optimizer_states(optimizer, hcg=None, mesh=None, axis_name="sharding"):
    """Stage 1: re-place every optimizer state tensor sharded on dim 0 along
    the sharding axis (reference: dygraph_sharding_optimizer.py:54 partitions
    params across ranks; here the state arrays themselves are sharded)."""
    if mesh is None:
        mesh = hcg.mesh
    degree = mesh.get_dim_size(axis_name) if axis_name in mesh.dim_names else 1
    if degree == 1:
        return optimizer
    for p in optimizer._parameter_list:
        st = optimizer._param_state(p)
        for k, v in list(st.items()):
            if hasattr(v, "ndim") and _shardable(v, degree):
                st[k] = jax.device_put(
                    v, mesh.sharding_for(_axis_placements(mesh, axis_name), v.ndim))
    return optimizer


def shard_gradients(model, mesh, axis_name="sharding"):
    """Stage 2 addition: as each leaf grad accumulates, re-place it sharded
    (the reference reduce-scatters grads, group_sharded_stage2.py:47)."""
    degree = mesh.get_dim_size(axis_name) if axis_name in mesh.dim_names else 1
    if degree == 1:
        return

    def make_hook(p):
        def hook(g):
            if _shardable(g._data, degree):
                g._data = jax.device_put(
                    g._data,
                    mesh.sharding_for(_axis_placements(mesh, axis_name), g.ndim))
            return g
        return hook

    for p in model.parameters():
        if not p.stop_gradient:
            p._grad_hooks.append(make_hook(p))


def stage2_gradient_fn(loss_fn, mesh, axis_name="sharding", batch_ndims=None):
    """Build the explicit ZeRO-2 gradient pipeline: data-parallel loss over
    the ``axis_name`` mesh axis with per-leaf gradients REDUCE-SCATTERED
    (``lax.psum_scatter`` on dim 0), never all-reduced — each rank leaves the
    step holding only its 1/degree grad shard, the stage-2 contract
    (reference: group_sharded_stage2.py:47 reduce-scatter hooks).

    loss_fn(params, *batch) -> scalar (mean over the local batch).
    Returns grad_fn(params, *batch) -> grads pytree whose dim-0-shardable
    leaves are sharded over ``axis_name`` (others replicated via psum).
    Wrap in jax.jit; batch args must have dim 0 divisible by the degree.
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    n = jmesh.shape[axis_name]

    def grad_fn(params, *batch):
        def local(params, *local_batch):
            g = jax.grad(loss_fn)(params, *local_batch)

            def rs(leaf):
                if leaf.ndim >= 1 and leaf.shape[0] % n == 0 \
                        and leaf.shape[0] >= n:
                    return lax.psum_scatter(leaf / n, axis_name,
                                            scatter_dimension=0, tiled=True)
                return lax.psum(leaf / n, axis_name)

            return jax.tree.map(rs, g)

        param_spec = jax.tree.map(lambda _: P(), params)
        batch_specs = tuple(P(axis_name) for _ in batch)
        out_spec = jax.tree.map(
            lambda l: P(axis_name) if (l.ndim >= 1 and l.shape[0] % n == 0
                                       and l.shape[0] >= n) else P(),
            params)
        return shard_map(local, mesh=jmesh,
                         in_specs=(param_spec,) + batch_specs,
                         out_specs=out_spec, check_vma=False)(params, *batch)

    return grad_fn


def shard_parameters(model, mesh, axis_name="sharding"):
    """Stage 3 addition: parameters themselves sharded on dim 0
    (reference: group_sharded_stage3.py:85)."""
    degree = mesh.get_dim_size(axis_name) if axis_name in mesh.dim_names else 1
    if degree == 1:
        return
    for p in model.parameters():
        if _shardable(p._data, degree):
            pl = _axis_placements(mesh, axis_name)
            p._data = jax.device_put(p._data, mesh.sharding_for(pl, p.ndim))
            p._dist_attr = (mesh, pl)


def group_sharded_parallel(model, optimizer, level, scaler=None, group=None,
                           offload=False, sync_buffers=False, buffer_max_size=2**23,
                           segment_size=2**20, sync_comm=False,
                           dp_group=None, exclude_layer=None):
    """Reference: python/paddle/distributed/sharding/group_sharded.py:50.
    level: "os" | "os_g" | "p_g_os"."""
    from .fleet.topology import get_hybrid_communicate_group
    hcg = get_hybrid_communicate_group()
    if hcg is not None:
        mesh, axis = hcg.mesh, "sharding"
    else:
        import numpy as np
        n = len(jax.devices())
        mesh, axis = ProcessMesh(np.arange(n), ["sharding"]), "sharding"
    if level not in ("os", "os_g", "p_g_os"):
        raise ValueError(f"level must be os|os_g|p_g_os, got {level}")
    shard_optimizer_states(optimizer, mesh=mesh, axis_name=axis)
    if level in ("os_g", "p_g_os"):
        shard_gradients(model, mesh, axis)
    if level == "p_g_os":
        shard_parameters(model, mesh, axis)
    return model, optimizer, scaler


def save_group_sharded_model(model, output, optimizer=None):
    """reference: distributed/sharding/group_sharded.py
    save_group_sharded_model — persist the UNsharded model (and
    optimizer) state from a group_sharded_parallel wrapper. GSPMD keeps
    parameters logically whole on this stack, so gathering is the
    identity; the artifact matches the reference layout
    (<output>.pdmodel params + <output>.pdopt optimizer)."""
    import os
    from ..framework.io import save as fsave
    os.makedirs(output, exist_ok=True)
    target = model
    inner = getattr(model, "_layers", None) or getattr(model, "inner", None)
    if inner is not None:
        target = inner
    fsave(target.state_dict(), os.path.join(output, "model.pdparams"))
    if optimizer is not None:
        state = optimizer.state_dict() if hasattr(optimizer, "state_dict") \
            else {}
        fsave(state, os.path.join(output, "model.pdopt"))


__all__ = [n for n in list(globals()) if not n.startswith("_")]
