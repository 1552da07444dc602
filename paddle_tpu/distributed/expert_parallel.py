"""Expert parallelism: explicit all-to-all MoE over the ``ep`` mesh axis.

TPU-native analog of the reference's expert-parallel data path
(reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:261
— custom NCCL all-to-all `global_scatter/global_gather`; moe group from
fleet topology). Here the path is a shard_map region: tokens are sharded
over ``ep``, each device gates its local tokens, ``jax.lax.all_to_all``
exchanges the [E, C, M] dispatch buffer so each device receives every
device's slice for ITS experts, local experts run, and the inverse
all-to-all brings expert outputs home — two ICI all-to-alls per layer,
exactly the reference's wire pattern but compiled into the XLA program.

For the fully-automatic path prefer MoELayer under GSPMD (sharding the
stacked expert weights over ``ep``) and let XLA insert the same
collectives; this module is the explicit form (and the one that scales to
cross-slice DCN meshes where manual placement matters).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..incubate.distributed.models.moe.gate import capacity_for


def _local_moe(x_local, gate_w, expert_params, *, expert_fn, top_k,
               capacity, ep_axis, n_exp_local, aux):
    """Per-device body. x_local: [T_local, M]; gate_w: [M, E] replicated;
    expert_params: pytree with leading axis n_exp_local (this device's
    experts)."""
    from ..incubate.distributed.models.moe.gate import topk_gating

    ep = jax.lax.axis_size(ep_axis)
    E = n_exp_local * ep
    logits = x_local @ gate_w                                    # [T, E]
    combine, aux_loss = topk_gating.pure(
        logits, top_k=top_k, capacity=capacity, normalize=True, aux=aux)
    mask = (combine > 0).astype(x_local.dtype)
    dispatched = jnp.einsum("tec,tm->ecm", mask, x_local)        # [E, C, M]
    # all-to-all: split the expert axis across ranks, concat the capacity
    # axis -> [E_local, C * ep, M]: every device now holds all ranks'
    # tokens for its local experts (rank-major along the capacity axis).
    recv = jax.lax.all_to_all(dispatched, ep_axis, split_axis=0,
                              concat_axis=1, tiled=True)
    outs = []
    for e in range(n_exp_local):
        p_e = jax.tree.map(lambda l, e=e: l[e], expert_params)
        outs.append(expert_fn(p_e, recv[e]))
    y = jnp.stack(outs)                                          # [El, C*ep, M]
    # inverse all-to-all: send each rank its tokens' outputs back
    back = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                              tiled=True)                        # [E, C, M]
    combined = jnp.einsum("tec,ecm->tm", combine.astype(x_local.dtype), back)
    return combined, jax.lax.pmean(aux_loss, ep_axis)


def moe_alltoall(x, gate_w, expert_params, expert_fn, mesh, ep_axis="ep",
                 top_k=2, capacity_factor=2.0, aux="gshard"):
    """Functional EP MoE: x [T, M] sharded over ``ep`` on axis 0;
    expert_params leaves [n_experts, ...] sharded over ``ep`` on axis 0.
    Returns (y [T, M], aux_loss). Call inside (or as) a jitted program.
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    ep = jmesh.shape[ep_axis]
    n_experts = jax.tree.leaves(expert_params)[0].shape[0]
    if n_experts % ep != 0:
        raise ValueError(f"n_experts {n_experts} not divisible by ep={ep}")
    t_local = x.shape[0] // ep
    capacity = capacity_for(t_local, n_experts, top_k, capacity_factor)
    body = functools.partial(
        _local_moe, expert_fn=expert_fn, top_k=top_k, capacity=capacity,
        ep_axis=ep_axis, n_exp_local=n_experts // ep, aux=aux)
    mapped = shard_map(
        body, mesh=jmesh,
        in_specs=(P(ep_axis, None), P(None, None), P(ep_axis)),
        out_specs=(P(ep_axis, None), P()), check_vma=False)
    y, aux_loss = mapped(x, gate_w, expert_params)
    return y, aux_loss


__all__ = ["moe_alltoall"]
