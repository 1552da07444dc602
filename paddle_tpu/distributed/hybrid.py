"""3-D hybrid-parallel Llama training: dp x mp x pp in ONE jitted program.

TPU-native analog of the reference's hybrid orchestration at its
north-star configuration (reference: fleet topology
python/paddle/distributed/fleet/base/topology.py:70 + PipelineParallel
meta_parallel/pipeline_parallel.py:684 + mp layers
fleet/layers/mpu/mp_layers.py — three separate runtime systems stitched
through NCCL groups). Here the whole 3-D step is one shard_map program:

- **pp**: decoder stages stacked on a leading axis, activations hop to the
  +1 ICI neighbor via ppermute (distributed/pipeline.py schedule math);
- **mp**: weights sharded on head/ffn dims; the stage function is
  TP-aware — column-parallel projections compute on local shards and the
  row-parallel outputs are combined with an explicit ``lax.psum`` over the
  mp axis (the Megatron pattern, compiler-visible);
- **dp**: the microbatch axis is sharded over dp; gradient averaging is a
  single ``psum`` at the loss, and optimizer states can shard over dp
  (ZeRO-1) by construction of the update.

``build_llama_hybrid`` returns pure ``init/step`` functions; jit ``step``
once and every training iteration is a single XLA executable with all
collectives visible to the scheduler.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models.generation import _rms_norm, _rope
from .pipeline import _interleaved_body


def _tp_block(pl, h, pos, cfg, mp_axis):
    """One decoder layer on LOCAL mp shards. pl holds weights whose
    head/ffn dims are already mp-local; row-parallel outputs psum over mp.
    """
    b, s, H = h.shape
    d = cfg.head_dim
    x = _rms_norm(h, pl["ln1"], cfg.rms_norm_eps)
    q = x @ pl["q"]
    k = x @ pl["k"]
    v = x @ pl["v"]
    h_loc = q.shape[-1] // d                        # local heads
    hkv_loc = k.shape[-1] // d
    q = q.reshape(b, s, h_loc, d)
    k = k.reshape(b, s, hkv_loc, d)
    v = v.reshape(b, s, hkv_loc, d)
    q = _rope(q, pos, cfg.rope_theta, d)
    k = _rope(k, pos, cfg.rope_theta, d)
    if hkv_loc != h_loc:
        k = jnp.repeat(k, h_loc // hkv_loc, axis=2)
        v = jnp.repeat(v, h_loc // hkv_loc, axis=2)
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(d))
    p = jax.nn.softmax(jnp.where(mask, scores, -1e30).astype(jnp.float32),
                       -1).astype(q.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, h_loc * d)
    attn_out = o @ pl["o"]                          # row-parallel: partial
    if mp_axis is not None:
        attn_out = jax.lax.psum(attn_out, mp_axis)
    h = h + attn_out
    x = _rms_norm(h, pl["ln2"], cfg.rms_norm_eps)
    ffn = (jax.nn.silu(x @ pl["gate"]) * (x @ pl["up"])) @ pl["down"]
    if mp_axis is not None:
        ffn = jax.lax.psum(ffn, mp_axis)            # row-parallel combine
    return h + ffn


def init_llama_params(cfg, n_stages, key=None):
    """Stacked per-stage params: leaves [n_stages, layers_per_stage, ...].

    Weight layout matches models/llama.py Linear ([in, out]).
    """
    if cfg.num_hidden_layers % n_stages:
        raise ValueError(
            f"{cfg.num_hidden_layers} layers not divisible by pp={n_stages}")
    lps = cfg.num_hidden_layers // n_stages
    key = key if key is not None else jax.random.key(0)
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    d = cfg.head_dim
    Hq, Hkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    ks = jax.random.split(key, 10)

    def w(k, shape, scale=None):
        scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
        return (jax.random.normal(k, (n_stages, lps) + shape, jnp.float32)
                * scale)

    stage = {
        "ln1": jnp.ones((n_stages, lps, H)),
        "q": w(ks[0], (H, Hq)), "k": w(ks[1], (H, Hkv)),
        "v": w(ks[2], (H, Hkv)), "o": w(ks[3], (Hq, H)),
        "ln2": jnp.ones((n_stages, lps, H)),
        "gate": w(ks[4], (H, I)), "up": w(ks[5], (H, I)),
        "down": w(ks[6], (I, H)),
    }
    embed = jax.random.normal(ks[7], (V, H), jnp.float32) * 0.02
    return {"stages": stage, "embed": embed, "norm": jnp.ones((H,))}


def _stage_specs(mp_axis):
    """PartitionSpecs for the stacked stage params: leading axis pp; mp on
    the head/ffn dim (column-parallel on out-dim, row-parallel on in-dim)."""
    col = P("pp", None, None, mp_axis)     # q/k/v/gate/up: shard out-dim
    row = P("pp", None, mp_axis, None)     # o/down: shard in-dim
    rep = P("pp", None, None)
    return {"ln1": rep, "q": col, "k": col, "v": col, "o": row,
            "ln2": rep, "gate": col, "up": col, "down": row}


def build_llama_hybrid(cfg, mesh, n_micro=4, lr=1e-3, schedule="1f1b"):
    """Returns (init_fn, step_fn, shardings).

    step_fn(params, opt_state, ids) -> (params, opt_state, loss); jit it
    with the returned shardings (or rely on with_sharding_constraint via
    GSPMD for the embed/norm leaves).
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    pp = jmesh.shape.get("pp", 1)
    has_mp = "mp" in jmesh.shape and jmesh.shape["mp"] > 1
    mp_axis = "mp" if has_mp else None
    lps = cfg.num_hidden_layers // pp
    fn = None  # built inside step

    def stage_fn(pl, x):
        """x: [mb, S, H] local; pl leaves [lps, ...] (stage axis consumed)."""
        pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                               (x.shape[0], x.shape[1]))
        for i in range(lps):
            pli = jax.tree.map(lambda l, i=i: l[i], pl)
            x = _tp_block(pli, x, pos, cfg, mp_axis)
        return x

    sspec = _stage_specs(mp_axis)
    # x: [n_micro, mb, S, H] — microbatch dim stays unsharded (the pipeline
    # loop consumes it), batch-within-microbatch shards over dp
    x_spec = P(None, "dp", None, None)

    def pipeline(stage_params, xm):
        body_fn = jax.checkpoint(stage_fn) if schedule in ("1f1b",
                                                           "interleaved") \
            else stage_fn
        body = functools.partial(
            _interleaved_body, fn=body_fn, axis_name="pp",
            n_micro=xm.shape[0], n_stages=pp, vpp=1)
        mapped = shard_map(
            body, mesh=jmesh,
            in_specs=(sspec, x_spec), out_specs=x_spec, check_vma=False)
        return mapped(stage_params, xm)

    def loss_fn(params, ids):
        B, S = ids.shape
        h = params["embed"][ids]                     # [B, S, H]
        mb = B // n_micro
        xm = h.reshape(n_micro, mb, S, cfg.hidden_size)
        ym = pipeline(params["stages"], xm)
        y = ym.reshape(B, S, cfg.hidden_size)
        y = _rms_norm(y, params["norm"], cfg.rms_norm_eps)
        logits = y @ params["embed"].T               # tied head
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), -1)
        tgt = ids[:, 1:]
        nll = -jnp.take_along_axis(logp, tgt[..., None], -1)[..., 0]
        return nll.mean()

    def init_fn(key=None):
        params = init_llama_params(cfg, pp, key)
        opt_state = {
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params),
            "t": jnp.zeros((), jnp.int32),
        }
        return params, opt_state

    def step_fn(params, opt_state, ids):
        loss, grads = jax.value_and_grad(loss_fn)(params, ids)
        t = opt_state["t"] + 1
        b1, b2, eps, wd = 0.9, 0.95, 1e-8, 0.01

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / (1 - b1 ** t.astype(jnp.float32))
            vh = v / (1 - b2 ** t.astype(jnp.float32))
            new_p = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
            return new_p, m, v

        flat_p, tree = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        flat_m = jax.tree.leaves(opt_state["m"])
        flat_v = jax.tree.leaves(opt_state["v"])
        out = [upd(p, g, m, v) for p, g, m, v in
               zip(flat_p, flat_g, flat_m, flat_v)]
        params = jax.tree.unflatten(tree, [o[0] for o in out])
        opt_state = {"m": jax.tree.unflatten(tree, [o[1] for o in out]),
                     "v": jax.tree.unflatten(tree, [o[2] for o in out]),
                     "t": t}
        return params, opt_state, loss

    def shardings():
        """NamedShardings for params (apply with jax.device_put)."""
        def ns(spec):
            return NamedSharding(jmesh, spec)
        stage_sh = {k: ns(v) for k, v in _stage_specs(mp_axis).items()}
        return {
            "stages": stage_sh,
            "embed": ns(P(None, None)),
            "norm": ns(P(None)),
        }

    return init_fn, step_fn, shardings


__all__ = ["build_llama_hybrid", "init_llama_params"]
