"""Activation recomputation (gradient checkpointing).

TPU-native analog of the reference's recompute
(reference: python/paddle/distributed/fleet/recompute/recompute.py:128
RecomputeFunction, :463 recompute, :630 recompute_sequential). Same PyLayer
design: forward runs without a tape and stores inputs + RNG state; backward
replays the function with recording on and pushes the incoming cotangents
through the replayed subgraph. On TPU the compiled path should prefer
``jax.checkpoint`` (exposed here as ``recompute_pure``) which lets XLA
rematerialize inside one fused program instead of host-side replay.
"""
from __future__ import annotations

import jax

from ...amp.auto_cast import amp_state as _amp_state
from ...autograd.py_layer import PyLayer, PyLayerContext
from ...core import autograd as _ag
from ...core import phase_scope
from ...core import random as _rng
from ...core.autograd import enable_grad, no_grad
from ...core.tensor import Tensor


class RecomputeFunction(PyLayer):
    # Always record: fn usually closes over trainable params, so a grad node
    # is needed even when every explicit tensor arg has stop_gradient=True.
    _force_record = True

    @staticmethod
    def forward(ctx, fn, preserve_rng_state, *args):
        ctx.fn = fn
        ctx.preserve_rng_state = preserve_rng_state
        if preserve_rng_state:
            ctx.rng_state = _rng.get_rng_state()
        # Snapshot AMP autocast state: backward() usually runs outside the
        # user's auto_cast block, so the replay must re-enter the forward's
        # AMP regime or every remat'd op recomputes in fp32 (the reference
        # saves amp_level/amp_dtype/amp lists the same way —
        # recompute.py:128 RecomputeFunction.forward -> amp_state()).
        st = _amp_state()
        ctx.amp = (st.enabled, st.dtype, st.level, st.white, st.black)
        # and the open phase (core/phase_scope.py), for the same reason:
        # the replay is charged where the forward was, marked recomputed
        ctx.phase = phase_scope.open_phase()
        ctx.inputs = args
        ctx.tensor_indices = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
        with no_grad():
            out = fn(*args)
        return out

    @staticmethod
    def backward(ctx, *grads):
        # Replay with fresh leaves so the inner tape stops at our inputs.
        detached = []
        for a in ctx.inputs:
            if isinstance(a, Tensor):
                d = Tensor(a._data, stop_gradient=a.stop_gradient)
                detached.append(d)
            else:
                detached.append(a)
        if ctx.preserve_rng_state:
            saved = _rng.get_rng_state()
            _rng.set_rng_state(ctx.rng_state)
        st = _amp_state()
        saved_amp = (st.enabled, st.dtype, st.level, st.white, st.black)
        (st.enabled, st.dtype, st.level, st.white, st.black) = ctx.amp
        try:
            with enable_grad(), phase_scope.reenter(ctx.phase, remat=True):
                out = ctx.fn(*detached)
        finally:
            (st.enabled, st.dtype, st.level, st.white, st.black) = saved_amp
            if ctx.preserve_rng_state:
                _rng.set_rng_state(saved)
        out_list = [out] if isinstance(out, Tensor) else [
            o for o in jax.tree.flatten(
                out, is_leaf=lambda x: isinstance(x, Tensor))[0]
            if isinstance(o, Tensor)]
        diff_inputs = [detached[i] for i in ctx.tensor_indices
                       if not detached[i].stop_gradient]
        roots = [o for o in out_list if not o.stop_gradient]
        seeds = [g for o, g in zip(out_list, grads) if not o.stop_gradient]
        # Full backward over the replayed subgraph so grads of closed-over
        # leaves (model params captured by fn) accumulate into their .grad —
        # the reference's backward does the same (recompute.py:128 calls
        # paddle.autograd.backward on the recomputed outputs).
        _ag.backward(roots, grad_tensors=seeds)
        # PyLayer.backward returns one grad per Tensor input of forward, in
        # order; forward's Tensor inputs are exactly the Tensor entries of
        # *args (fn / preserve_rng_state are non-tensor leaves).
        sink = _ag._grad_sink
        result = []
        for i in ctx.tensor_indices:
            d = detached[i]
            if d.stop_gradient:
                result.append(None)
            elif sink is not None:
                g = sink.pop(id(d), None)
                result.append(Tensor(g, stop_gradient=True) if g is not None else None)
            else:
                result.append(d.grad)
        return tuple(result)


def recompute(function, *args, **kwargs):
    """Run ``function`` without saving activations; recompute in backward."""
    preserve = kwargs.pop("preserve_rng_state", True)
    kwargs.pop("use_reentrant", None)
    if kwargs:
        fn = lambda *a: function(*a, **kwargs)
    else:
        fn = function
    if not _ag.is_grad_enabled():
        return fn(*args)
    return RecomputeFunction.apply(fn, preserve, *args)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Segmented recompute over a Sequential-like list of layers
    (reference: recompute.py:630)."""
    segments = (ctx or {}).get("segments", 1) if isinstance(ctx, dict) else 1
    if hasattr(functions, "children"):
        functions = list(functions.children())
    functions = list(functions)
    n = len(functions)
    seg_size = max(1, n // max(1, segments))

    def run_segment(start, end):
        def seg_fn(*inputs):
            out = inputs
            for f in functions[start:end]:
                out = f(*out) if isinstance(out, tuple) else f(out)
                if not isinstance(out, tuple):
                    out = (out,)
            return out if len(out) > 1 else out[0]
        return seg_fn

    out = args
    start = 0
    while start < n:
        end = min(start + seg_size, n)
        seg = run_segment(start, end)
        out = recompute(seg, *out, **kwargs)
        if not isinstance(out, tuple):
            out = (out,)
        start = end
    return out if len(out) > 1 else out[0]


def recompute_pure(fn, policy=None, prevent_cse=True):
    """``jax.checkpoint`` for the compiled path: XLA-level rematerialization.

    The idiomatic TPU form of recompute — use inside ``paddle_tpu.jit``
    programs; trades FLOPs for HBM exactly like the reference's static-graph
    recompute pass (python/paddle/distributed/passes/auto_parallel_recompute.py).
    """
    return jax.checkpoint(fn, policy=policy, prevent_cse=prevent_cse)


__all__ = ["recompute", "recompute_sequential", "recompute_pure", "RecomputeFunction"]
