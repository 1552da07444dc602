"""Context parallelism: ring attention + Ulysses (all-to-all) attention.

The reference has NO ring attention (SURVEY.md §5: "No ring-attention/
blockwise-CP implementation in-tree" — its long-context story is
Megatron-SP scatter/gather (fleet/utils/sequence_parallel_utils.py) plus a
'sep' mesh axis whose sequence split is model-side
(fleet/base/topology.py:77, meta_parallel/segment_parallel.py:26)).
This module fills that gap TPU-natively:

- ``ring_attention`` — blockwise attention over the ``sep`` axis. Each
  device holds a contiguous sequence shard; k/v chunks rotate around the
  ring via ``jax.lax.ppermute`` (collective-permute = ICI-neighbor DMA)
  while each hop's partial attention is combined online via logsumexp
  weights. Backward is a second ring pass (flash-style recomputation from
  the combined lse) with gradient chunks riding the same ring — memory
  stays O(s_local), never O(s^2) or O(s_global).
- ``ulysses_attention`` — Ulysses-style sequence parallelism: all-to-all
  swaps the shard axis from sequence to heads, full-sequence flash
  attention runs locally, and a second all-to-all swaps back.

Both compose with the GSPMD path (they are shard_map regions inside the
jitted train step) and run the Pallas flash kernel per block on TPU (jnp
composition on CPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

_BIG = 1e30


# ---------------------------------------------------------------------------
# per-block attention engines ([b, h, s, d] layout)
# ---------------------------------------------------------------------------

def _block_fwd(q, k, v, causal, scale, impl):
    """Returns (out, lse[b,h,s]) for one (q-shard, kv-chunk) pair."""
    if impl == "pallas" or impl == "pallas_interpret":
        from ..kernels.flash_attention import flash_attention_with_lse
        return flash_attention_with_lse(
            q, k, v, causal=causal, scale=scale,
            interpret=(impl == "pallas_interpret"))
    # jnp composition (CPU tests / short shards)
    hq, hkv = q.shape[1], k.shape[1]
    if hq != hkv:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(qi >= ki, s, -_BIG)
    m = jnp.max(s, axis=-1)                          # [b,h,sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v) / \
        l[..., None].astype(v.dtype)
    return out.astype(q.dtype), m + jnp.log(l)


def _block_bwd(q, k, v, do, lse, delta, causal, scale, impl):
    """Returns (dq, dk, dv) given combined lse/delta (flash recompute)."""
    if impl == "pallas" or impl == "pallas_interpret":
        from ..kernels.flash_attention import _bwd_impl
        return _bwd_impl(q, k, v, do, lse, delta, scale=scale, causal=causal,
                         block_q=128, block_k=128,
                         interpret=(impl == "pallas_interpret"))
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    kf = jnp.repeat(k, group, axis=1) if group > 1 else k
    vf = jnp.repeat(v, group, axis=1) if group > 1 else v
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * scale
    if causal:
        sk = s.shape[-1]
        qi = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + (sk - sq)
        ki = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(qi >= ki, s, -_BIG)
    p = jnp.exp(s - lse[..., None])                       # [b,h,sq,sk]
    dof = do.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf.astype(jnp.float32))
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf.astype(jnp.float32)) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32)) * scale
    if group > 1:
        dk = dk.reshape(b, hkv, group, *dk.shape[2:]).sum(axis=2)
        dv = dv.reshape(b, hkv, group, *dv.shape[2:]).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# ring attention (inside shard_map; [b, h, s_local, d] per device)
# ---------------------------------------------------------------------------

def _ring_fwd_pass(q, k, v, axis_name, causal, scale, impl):
    n = jax.lax.psum(1, axis_name)
    r = jax.lax.axis_index(axis_name)
    shift = [(i, (i + 1) % n) for i in range(n)]

    outs, lses = [], []
    kv = (k, v)
    for j in range(n):
        kj, vj = kv
        # after j hops the chunk on this device originated at rank r - j
        oi, li = _block_fwd(q, kj, vj, causal and j == 0, scale, impl)
        if causal and j > 0:
            # chunk r-j is entirely in the past iff j <= r; else invisible
            li = jnp.where(j <= r, li, -_BIG)
        outs.append(oi)
        lses.append(li)
        if j < n - 1:
            kv = jax.lax.ppermute(kv, axis_name, shift)

    lse_all = jnp.stack(lses)                      # [n, b, h, s]
    lse_tot = jax.scipy.special.logsumexp(lse_all, axis=0)
    w = jnp.exp(lse_all - lse_tot[None])           # [n, b, h, s]
    out = sum(o * wi[..., None].astype(o.dtype)
              for o, wi in zip(outs, w))
    return out.astype(q.dtype), lse_tot


def _ring_bwd_pass(q, k, v, out, lse_tot, do, axis_name, causal, scale, impl):
    n = jax.lax.psum(1, axis_name)
    r = jax.lax.axis_index(axis_name)
    shift = [(i, (i + 1) % n) for i in range(n)]

    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq = jnp.zeros(q.shape, jnp.float32)
    ring = (k, v, jnp.zeros(k.shape, jnp.float32),
            jnp.zeros(v.shape, jnp.float32))
    for j in range(n):
        kj, vj, dkj, dvj = ring
        if causal and j > 0:
            # push lse to +BIG on invisible chunks: p = exp(s - lse) -> 0
            lse_eff = lse_tot + jnp.where(j <= r, 0.0, _BIG)
        else:
            lse_eff = lse_tot
        dq_p, dk_p, dv_p = _block_bwd(q, kj, vj, do, lse_eff, delta,
                                      causal and j == 0, scale, impl)
        dq = dq + dq_p.astype(jnp.float32)
        ring = (kj, vj, dkj + dk_p.astype(jnp.float32),
                dvj + dv_p.astype(jnp.float32))
        # one more rotation than the fwd loop: the last hop returns each
        # chunk's accumulated dk/dv to its owner (chunk c sits at rank
        # c + n - 1 after the loop; one shift brings it home).
        ring = jax.lax.ppermute(ring, axis_name, shift)
    _, _, dk, dv = ring
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _make_ring(axis_name, causal, scale, impl):
    @jax.custom_vjp
    def ring(q, k, v):
        out, _ = _ring_fwd_pass(q, k, v, axis_name, causal, scale, impl)
        return out

    def ring_fwd(q, k, v):
        out, lse = _ring_fwd_pass(q, k, v, axis_name, causal, scale, impl)
        return out, (q, k, v, out, lse)

    def ring_bwd(res, g):
        q, k, v, out, lse = res
        return _ring_bwd_pass(q, k, v, out, lse, g, axis_name, causal,
                              scale, impl)

    ring.defvjp(ring_fwd, ring_bwd)
    return ring


def _auto_impl(interpret=None):
    if interpret is not None:
        return "pallas_interpret" if interpret else "pallas"
    return "pallas" if jax.devices()[0].platform not in ("cpu", "gpu") \
        else "xla"


def ring_attention_p(q, k, v, mesh, axis_name="sep", causal=True, scale=None,
                     impl=None):
    """Pure ring attention over sequence-sharded [b, s, h, d] arrays.

    ``q/k/v`` are GLOBAL arrays (or global-view DTensors inside jit);
    shard_map splits them along ``axis_name`` over the sequence dim.
    Differentiable; use inside jit. ``impl``: None (auto), "pallas",
    "pallas_interpret", or "xla".
    """
    impl = impl or _auto_impl()
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    jmesh = getattr(mesh, "jax_mesh", mesh)

    ring = _make_ring(axis_name, causal, scale, impl)

    def body(qh, kh, vh):
        # [b, s_loc, h, d] -> kernel layout
        o = ring(jnp.swapaxes(qh, 1, 2), jnp.swapaxes(kh, 1, 2),
                 jnp.swapaxes(vh, 1, 2))
        return jnp.swapaxes(o, 1, 2)

    spec = P(None, axis_name, None, None)
    fn = shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Ulysses (all-to-all) sequence parallelism
# ---------------------------------------------------------------------------

def ulysses_attention_p(q, k, v, mesh, axis_name="sep", causal=True,
                        scale=None, impl=None):
    """Ulysses attention: seq-sharded -> head-sharded via all-to-all, local
    full-sequence flash attention, then back. Heads must divide the axis
    size. Reference analog: the 'sep' axis P8 (segment parallel) whose
    attention the reference leaves to the model; here it is a drop-in
    functional."""
    impl = impl or _auto_impl()
    d = q.shape[-1]
    scale = float(scale) if scale is not None else 1.0 / math.sqrt(d)
    jmesh = getattr(mesh, "jax_mesh", mesh)

    def body(qh, kh, vh):
        # [b, s_loc, h, d] -> [b, s_full, h_loc, d]
        def a2a(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                      concat_axis=1, tiled=True)

        def a2a_back(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                      concat_axis=2, tiled=True)

        qg, kg, vg = a2a(qh), a2a(kh), a2a(vh)
        if impl in ("pallas", "pallas_interpret"):
            from ..kernels.flash_attention import flash_attention
            o = flash_attention(jnp.swapaxes(qg, 1, 2),
                                jnp.swapaxes(kg, 1, 2),
                                jnp.swapaxes(vg, 1, 2), causal=causal,
                                scale=scale,
                                interpret=(impl == "pallas_interpret"))
            o = jnp.swapaxes(o, 1, 2)
        else:
            from ..nn.functional.attention import _sdpa_reference
            o = _sdpa_reference(qg, kg, vg, causal=causal, scale=scale)
        return a2a_back(o)

    spec = P(None, axis_name, None, None)
    fn = shard_map(body, mesh=jmesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# eager Tensor surface
# ---------------------------------------------------------------------------

def ring_attention(q, k, v, mesh=None, axis_name="sep", causal=True,
                   scale=None, impl=None):
    """Eager/Tensor surface for ring attention (paddle layout [b,s,h,d])."""
    from ..core.dispatch import eager_apply
    from .mesh import get_mesh
    mesh = mesh or get_mesh()
    return eager_apply(
        "ring_attention",
        lambda q_, k_, v_: ring_attention_p(q_, k_, v_, mesh, axis_name,
                                            causal, scale, impl),
        (q, k, v), {})


def ulysses_attention(q, k, v, mesh=None, axis_name="sep", causal=True,
                      scale=None, impl=None):
    """Eager/Tensor surface for Ulysses attention (paddle layout)."""
    from ..core.dispatch import eager_apply
    from .mesh import get_mesh
    mesh = mesh or get_mesh()
    return eager_apply(
        "ulysses_attention",
        lambda q_, k_, v_: ulysses_attention_p(q_, k_, v_, mesh, axis_name,
                                               causal, scale, impl),
        (q, k, v), {})


__all__ = [
    "ring_attention", "ring_attention_p",
    "ulysses_attention", "ulysses_attention_p",
]
