"""Generic 3-D hybrid parallelism for arbitrary ``nn.Layer`` models.

TPU-native analog of the reference's generic pipeline-model path
(reference: PipelineLayer stage partitioning
fleet/meta_parallel/parallel_layers/pp_layers.py:258 + PipelineParallel
meta_parallel/pipeline_parallel.py:684 + the mp layer library
fleet/layers/mpu/mp_layers.py), replacing the hand-written
per-architecture step of distributed/hybrid.py.

Shape of the rebuild — ONE jitted program over a dp x mp x pp mesh using
*partial-manual* shard_map (jax ``axis_names={'pp'}``):

- **pp (manual)**: the repeated blocks' parameter trees are extracted from
  the real ``nn.Layer`` objects (the same functionalization the compiled
  TrainStep uses) and stacked on a leading layer axis sharded over ``pp``;
  inside shard_map each stage loops its local blocks and activations hop
  +1 stage via ``ppermute`` (pipeline.py schedule math).
- **mp / dp (auto)**: stay GSPMD axes. Trailing dims of the stacked leaves
  keep their declared shardings (ColumnParallelLinear / RowParallelLinear
  plans work unchanged — the compiler inserts the Megatron collectives
  inside each stage), and the batch shards over dp. This is what makes the
  path generic: no per-architecture TP math is rewritten by hand.
- Embedding/head (or any heterogeneous prologue/epilogue layers) run
  OUTSIDE the pipelined region as ordinary GSPMD ops.

Constraints and capabilities:
- Blocks must be architecturally uniform (same parameter structure —
  true of the transformer stacks 3-D parallelism targets, and the same
  assumption the reference's LayerDesc lists make in practice).
- Blocks may map a TUPLE of activations to a same-structure tuple
  (multi-tensor stage boundaries — pp_layers.py multi-output stages);
  the pipeline buffers/permutes pytrees.
- Dropout (any RNG op) inside the pipelined region is supported on the
  circular schedules: pass ``rng_key`` to the step; each (microbatch,
  stage-application) derives its own fold — the reference's RNG tracker
  role (meta_parallel get_rng_state_tracker).
- Tied embeddings: ``loss_takes_params=True`` hands loss_fn the full
  param tree, so a head can reuse ``params['embed']`` and gradients
  accumulate from both uses (pp_layers.py:258 shared_weight semantics).
- The EXPLICIT-schedule path (zbh1/zbv/interleaved) keeps the v1
  single-tensor deterministic constraints.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..core.tensor import Tensor
from .pipeline import _interleaved_body


def _layer_state(layer):
    """name -> param Tensor for a Layer (buffers treated as constants)."""
    return dict(layer.named_parameters())


def functionalize(layer, n_inputs=1):
    """(arrays, apply_fn): pure apply over the layer's extracted params.

    apply_fn(arrs, *inputs, rng=None) runs the layer's real forward with
    ``arrays`` installed — the TrainStep functionalization
    (jit/__init__.py) reused at layer granularity. ``rng`` seeds the
    layer's stateful random ops (dropout) for that application; inputs
    and outputs may be pytrees (tuples of arrays).
    """
    import contextlib

    from ..core import random as _rng
    from ..jit import _Installed

    tensors = _layer_state(layer)
    arrays = {k: t._data for k, t in tensors.items()}

    def apply_fn(arrs, *inputs, rng=None):
        inst = _Installed(tensors)
        ctx = _rng.capture_rng(rng) if rng is not None \
            else contextlib.nullcontext()
        with inst, ctx:
            inst.install(arrs)
            out = layer(*jax.tree.map(
                lambda x: Tensor(x) if not isinstance(x, Tensor) else x,
                tuple(inputs), is_leaf=lambda x: not isinstance(
                    x, (tuple, list))))
        return jax.tree.map(
            lambda o: o._data if isinstance(o, Tensor) else o, out,
            is_leaf=lambda o: isinstance(o, Tensor))

    return arrays, apply_fn


def stack_block_params(blocks):
    """Stack per-block param trees: {name: [n_blocks, ...]}.

    Blocks must share a parameter structure; mp-sharded leaves stack into
    arrays whose trailing dims keep their GSPMD sharding.
    """
    states = [_layer_state(b) for b in blocks]
    keys = set(states[0])
    for i, st in enumerate(states[1:], 1):
        if set(st) != keys:
            raise ValueError(
                f"block {i} parameter structure {sorted(st)} differs from "
                f"block 0 {sorted(keys)} — pipelined blocks must be uniform")
    return {k: jnp.stack([st[k]._data for st in states]) for k in states[0]}


def build_hybrid_step(blocks, loss_fn, mesh, embed=None, head=None,
                      n_micro=4, schedule="1f1b", pp_axis="pp",
                      dp_axis="dp", vpp=1, loss_takes_params=False):
    """Build the single-program 3-D step for an arbitrary uniform-block model.

    blocks: list of nn.Layer, each mapping [mb, ...] -> [mb, ...] (built
    with mp layers for tensor parallelism — their GSPMD shardings ride
    through). embed/head: optional nn.Layer prologue/epilogue (run outside
    the pipeline). loss_fn(y_arrays, labels_arrays) -> scalar.

    Schedules:
      ``fthenb`` / ``1f1b`` — the circular shard_map pipeline (remat under
      1f1b), differentiated by outer AD.
      ``1f1b_zb`` (alias ``zbh1``) / ``zbv`` / ``interleaved`` — the
      EXPLICIT schedule executor (pipeline_schedule.py): static op tables,
      true 1F1B/zero-bubble execution with the B_INPUT/B_WEIGHT split, vpp
      chunks per stage (``interleaved`` needs vpp>1; ``zbv`` forces
      vpp=2). Constraint: ``head`` must be None on this path (fold the
      projection into ``loss_fn``); the embedding is differentiated through
      the executor's input-grad.

    Returns (params, step_fn) with step_fn(params, x, labels) ->
    (loss, grads): jit it once; grads match the params tree. x: [B, ...]
    with B divisible by n_micro (and the dp degree).
    """
    jmesh = getattr(mesh, "jax_mesh", mesh)
    pp = jmesh.shape.get(pp_axis, 1)
    n_blocks = len(blocks)
    explicit = schedule in ("1f1b_zb", "zbh1", "zbv", "interleaved")
    if schedule == "zbv":
        vpp = 2
    if schedule == "interleaved" and vpp < 2:
        raise ValueError("schedule='interleaved' needs vpp>=2 "
                         "(vpp=1 is plain 1F1B)")
    if explicit:
        if head is not None:
            raise ValueError(
                f"schedule {schedule!r} runs loss_fn on the last stage; "
                "fold the head into loss_fn (head=None)")
        if n_blocks % (pp * vpp):
            raise ValueError(
                f"{n_blocks} blocks not divisible by pp*vpp={pp * vpp}")
        lps = n_blocks // (pp * vpp)
    else:
        if vpp != 1:
            raise ValueError(
                f"schedule {schedule!r} (circular pipeline) does not take "
                "vpp>1 — use schedule='interleaved'/'zbv' for virtual "
                "chunks")
        if n_blocks % pp:
            raise ValueError(f"{n_blocks} blocks not divisible by pp={pp}")
        lps = n_blocks // pp
        if schedule not in ("fthenb", "1f1b"):
            raise ValueError(f"unknown schedule {schedule!r}")

    stacked = stack_block_params(blocks)
    _, block_apply = functionalize(blocks[0])
    params = {}
    embed_apply = head_apply = None
    if embed is not None:
        params["embed"], embed_apply = functionalize(embed)
    if head is not None:
        params["head"], head_apply = functionalize(head)

    def stage_fn(stage_arrays, x, rng=None):
        # stage_arrays leaves: [lps, ...] (stage/chunk axes consumed);
        # x may be one array or a tuple of arrays (multi-tensor boundary)
        for i in range(lps):
            args = x if isinstance(x, tuple) else (x,)
            sub = None if rng is None else jax.random.fold_in(rng, i)
            x = block_apply(
                jax.tree.map(lambda l, i=i: l[i], stage_arrays),
                *args, rng=sub)
        return x

    if explicit:
        # leaves [n_blocks, ...] -> [pp*vpp, lps, ...] in LAYER order; the
        # executor permutes virtual stages into its (stage, chunk) layout
        params["blocks"] = jax.tree.map(
            lambda l: l.reshape((pp * vpp, lps) + l.shape[1:]), stacked)
        from .pipeline_schedule import scheduled_pipeline_loss
        kind = {"1f1b_zb": "zbh1", "interleaved": "1f1b"}.get(
            schedule, schedule)

        def step_fn(params, x, labels):
            def loss(params):
                h = embed_apply(params["embed"], x) if embed_apply else x
                mb = h.shape[0] // n_micro
                xm = h.reshape((n_micro, mb) + h.shape[1:])
                lm = labels.reshape((n_micro, mb) + labels.shape[1:])
                # total = SUM of per-microbatch loss_fn(y_mb, labels_mb)
                # (divide by n_micro in loss_fn for mean semantics)
                return scheduled_pipeline_loss(
                    params["blocks"], xm, lm, stage_fn, loss_fn,
                    jmesh, axis_name=pp_axis, schedule=kind, vpp=vpp)

            return jax.value_and_grad(loss)(params)

        return params, step_fn

    # two-level stage layout [pp, lps, ...]: shard_map consumes the pp axis,
    # _interleaved_body the chunk axis, stage_fn loops the lps axis
    params["blocks"] = jax.tree.map(
        lambda l: l.reshape((pp, lps) + l.shape[1:]), stacked)
    block_specs = jax.tree.map(lambda _: P(pp_axis), params["blocks"])

    def pipeline(stage_params, xm, rng_key):
        base = jax.checkpoint(stage_fn) if schedule == "1f1b" else stage_fn
        body = functools.partial(
            _interleaved_body, fn=base, axis_name=pp_axis,
            n_micro=jax.tree.leaves(xm)[0].shape[0], n_stages=pp, vpp=1,
            rng_key=rng_key)
        x_spec = jax.tree.map(lambda l: P(*([None] * l.ndim)), xm)
        mapped = shard_map(body, mesh=jmesh,
                           in_specs=(block_specs, x_spec), out_specs=x_spec,
                           axis_names={pp_axis}, check_vma=False)
        return mapped(stage_params, xm)

    def step_fn(params, x, labels, rng_key=None):
        def loss(params):
            h = embed_apply(params["embed"], x) if embed_apply else x
            # h may be a tuple tree (multi-tensor stage boundary)
            def to_micro(l):
                mb = l.shape[0] // n_micro
                return l.reshape((n_micro, mb) + l.shape[1:])
            xm = jax.tree.map(to_micro, h)
            ym = pipeline(params["blocks"], xm, rng_key)
            y = jax.tree.map(
                lambda l: l.reshape((l.shape[0] * l.shape[1],)
                                    + l.shape[2:]), ym)
            if head_apply:
                args = y if isinstance(y, tuple) else (y,)
                y = head_apply(params["head"], *args)
            if loss_takes_params:
                return loss_fn(params, y, labels)
            return loss_fn(y, labels)

        return jax.value_and_grad(loss)(params)

    return params, step_fn


def load_stacked_into_blocks(blocks, stacked):
    """Write trained stacked params ([pp, lps, ...] layout) back into the
    Layer objects."""
    for i, b in enumerate(blocks):
        for k, t in _layer_state(b).items():
            leaf = stacked[k]
            flat = leaf.reshape((-1,) + leaf.shape[2:])
            t._data = flat[i]


__all__ = ["build_hybrid_step", "stack_block_params", "functionalize",
           "load_stacked_into_blocks"]
