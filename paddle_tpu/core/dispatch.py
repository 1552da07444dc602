"""Eager op dispatch.

TPU-native analog of the reference's generated ``<op>_ad_func`` layer +
kernel dispatch (reference: paddle/fluid/eager/auto_code_generator/generator/
eager_gen.py:374; paddle/phi/core/kernel_factory.h:58). Where the reference
generates per-op C++ forward functions from YAML, here every op is a pure
jnp/lax function wrapped by ``primitive``: the wrapper unwraps Tensors,
runs the function (under ``jax.vjp`` when any input requires grad), wraps
outputs, and wires GradNode edges. The "kernel registry" collapses to: the
op's body is its XLA lowering; Pallas kernels override bodies where a
hand-tuned path exists (paddle_tpu/kernels/).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import autograd, phase_scope
from .flags import GLOBAL_FLAGS
from .tensor import Tensor

# Op registry: name -> pure function. Pallas/hand-tuned kernels replace
# entries here (the analog of PD_REGISTER_KERNEL overriding a backend).
OPS: dict[str, callable] = {}


def _is_diff_array(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


def _maybe_amp_cast(name, vals):
    from ..amp.auto_cast import _state as _amp_state, amp_cast_inputs
    if not _amp_state.enabled:
        return vals
    return amp_cast_inputs(name, vals)


def _under_phase(name, vjp_fn):
    def scoped_vjp(cts):
        with phase_scope.reenter(name):
            return vjp_fn(cts)
    return scoped_vjp


# Set by paddle_tpu.profiler while a Profiler is active: (begin_fn, end_fn)
# where begin_fn(op_name) -> token and end_fn(token). Kept as one attribute
# so the disabled-path cost is a single None check per op.
PROFILE_HOOK = None

# Set by paddle_tpu.amp.debugging while operator-stats collection is active:
# fn(op_name, [input dtype strings]). One None check per op when disabled.
OP_STATS_HOOK = None


def eager_apply(name: str, pure_fn, args: tuple, kwargs: dict):
    """Execute ``pure_fn`` over a mixed Tensor/array argument tree.

    Tensors may appear anywhere in args/kwargs (including inside lists).
    Returns Tensors mirroring the output structure.
    """
    hook = PROFILE_HOOK  # read once: another thread may clear it mid-op
    if hook is not None:
        tok = hook[0](name)
        try:
            return _eager_apply_inner(name, pure_fn, args, kwargs)
        finally:
            hook[1](tok)
    return _eager_apply_inner(name, pure_fn, args, kwargs)


def _eager_apply_inner(name: str, pure_fn, args: tuple, kwargs: dict):
    if GLOBAL_FLAGS.get("dygraph_debug"):
        from .vlog import vlog
        vlog(1, f"eager op dispatch: {name}", component="eager")
    flat, treedef = jax.tree.flatten((args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    tensor_idx = [i for i, x in enumerate(flat) if isinstance(x, Tensor)]
    if OP_STATS_HOOK is not None:
        from ..amp.auto_cast import _state as _amp_s
        cast_to = None   # the dtype AMP will cast float inputs to, if any
        if _amp_s.enabled:
            if name in _amp_s.white:
                cast_to = _amp_s.dtype
            elif name in _amp_s.black:
                cast_to = jnp.float32
        OP_STATS_HOOK(name,
                      [str(flat[i]._data.dtype) for i in tensor_idx],
                      cast_to)
    record = autograd.is_grad_enabled() and any(
        not flat[i].stop_gradient for i in tensor_idx
    )

    if not record:
        vals = [x._data if isinstance(x, Tensor) else x for x in flat]
        vals = _maybe_amp_cast(name, vals)
        a, kw = jax.tree.unflatten(treedef, vals)
        out = pure_fn(*a, **kw)
        return _wrap_outputs(name, out, stop_gradient=True)

    # Differentiable path: vjp over the inexact tensor inputs.
    diff_idx = [i for i in tensor_idx
                if not flat[i].stop_gradient and _is_diff_array(flat[i]._data)]
    diff_tensors = [flat[i] for i in diff_idx]
    diff_arrays = [t._data for t in diff_tensors]
    base_vals = [x._data if isinstance(x, Tensor) else x for x in flat]

    def g(*primals):
        vals = list(base_vals)
        for i, p in zip(diff_idx, primals):
            vals[i] = p
        # AMP cast inside the traced fn so AD differentiates through it
        # (the reference casts in the generated ad_func, eager_gen.py:652).
        vals = _maybe_amp_cast(name, vals)
        a, kw = jax.tree.unflatten(treedef, vals)
        return pure_fn(*a, **kw)

    hooks = autograd.SAVED_TENSOR_HOOKS
    if hooks:
        # saved_tensors_hooks active (reference: python/paddle/autograd/
        # saved_tensors_hooks, eager pack/unpack hooks in
        # paddle/fluid/eager/saved_tensors_hooks.h): apply pack to every
        # array this node would keep for backward, and defer linearization
        # to backward time — unpack, then re-derive the vjp (checkpoint
        # semantics: one extra forward per op, the TPU-idiomatic trade
        # jax.checkpoint makes).
        pack, unpack = hooks[-1]
        out = g(*diff_arrays)
        packed = [pack(Tensor(a, stop_gradient=True)) for a in diff_arrays]
        # snapshot the AMP decision NOW: the deferred re-linearization must
        # differentiate the same (possibly autocast) function the forward
        # ran, even if backward happens outside the amp.auto_cast context
        from ..amp.auto_cast import _state as _amp_s
        amp_snap = (_amp_s.enabled, _amp_s.dtype, _amp_s.level,
                    _amp_s.white, _amp_s.black)
        # and the open phase (phase_scope.py): the re-run forward and
        # its backward are charged where the first forward was
        phase_snap = phase_scope.open_phase()

        def vjp_fn(cts, _g=g, _packed=packed, _unpack=unpack,
                   _amp=amp_snap, _phase=phase_snap):
            arrays = []
            for p in _packed:
                u = _unpack(p)
                arrays.append(u._data if isinstance(u, Tensor) else
                              jnp.asarray(u))
            from ..amp.auto_cast import _state as _s
            saved = (_s.enabled, _s.dtype, _s.level, _s.white, _s.black)
            (_s.enabled, _s.dtype, _s.level, _s.white, _s.black) = _amp
            try:
                with phase_scope.reenter(_phase, remat=True):
                    _, inner = jax.vjp(_g, *arrays)
            finally:
                (_s.enabled, _s.dtype, _s.level, _s.white,
                 _s.black) = saved
            with phase_scope.reenter(_phase):
                return inner(cts)
    else:
        out, vjp_fn = jax.vjp(g, *diff_arrays)
        # vjp_fn runs later, outside any ``with`` of the forward: JAX
        # keeps on the linearised equations the scopes opened INSIDE
        # ``g`` only, so the phase open round this op is carried here
        open_phase = phase_scope.open_phase()
        if open_phase is not None:
            vjp_fn = _under_phase(open_phase, vjp_fn)

    edges = []
    for t in diff_tensors:
        if t._grad_node is not None:
            edges.append(("node", t._grad_node, t._output_slot))
        else:
            edges.append(("leaf", t))

    flat_out, out_treedef = jax.tree.flatten(out)
    out_avals = [(o.shape, o.dtype) for o in flat_out]
    node = autograd.GradNode(name, vjp_fn, edges, out_avals, out_treedef)
    # replay info for double backward (create_graph=True): the pure primal
    # fn + the live input tensors, so the backward pass can re-derive the
    # vjp THROUGH the eager layer and land grads-of-grads on the tape
    # (the reference's double-grad ops, general_grad.h)
    node.replay = (g, diff_tensors)
    return _wrap_outputs(name, out, stop_gradient=False, node=node)


def _wrap_outputs(name, out, stop_gradient, node=None):
    flat_out, out_treedef = jax.tree.flatten(out)
    if GLOBAL_FLAGS.get("check_kernel_launch"):
        # surface async execution errors at the op that launched them
        # (reference FLAGS_check_kernel_launch: sync after every launch)
        for o in flat_out:
            if not isinstance(o, jax.core.Tracer):
                jax.block_until_ready(o)
    if GLOBAL_FLAGS.get("check_nan_inf"):
        for o in flat_out:
            # eager sweep only on concrete arrays; under a trace the
            # compiled path (TrainStep) carries its own fused finite check
            if jnp.issubdtype(o.dtype, jnp.inexact) \
                    and not isinstance(o, jax.core.Tracer) \
                    and not bool(jnp.isfinite(o).all()):
                raise FloatingPointError(
                    f"NaN/Inf detected in output of op '{name}'")
    wrapped = []
    for slot, o in enumerate(flat_out):
        t = Tensor(o, stop_gradient=True)
        if not stop_gradient and node is not None and _is_diff_array(o):
            t._grad_node = node
            t._output_slot = slot
            t.stop_gradient = False
        wrapped.append(t)
    return jax.tree.unflatten(out_treedef, wrapped)


def primitive(name=None):
    """Decorator registering a pure jnp function as an eager op.

    The decorated function must be pure (arrays in, arrays/pytree out) and
    traceable by JAX; the wrapper gives it eager Tensor semantics + autograd.
    The raw pure function remains reachable at ``wrapper.pure`` for the
    compiled path (paddle_tpu.jit) which traces whole programs instead.
    """

    def deco(fn):
        op_name = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return eager_apply(op_name, OPS[op_name], args, kwargs)

        OPS[op_name] = fn
        wrapper.pure = fn
        wrapper.op_name = op_name
        return wrapper

    return deco


def op_body(name: str):
    """Register a module-level function as an op's default body at import
    time (the analog of ``PD_REGISTER_KERNEL``'s static registration,
    reference paddle/phi/core/kernel_registry.h:196). The body takes arrays
    positionally and op settings as keyword-only arguments — the signature
    ``override_kernel`` replacements must match. Pair with ``op_call`` at
    the public API site so the body is resolved from ``OPS`` per call.
    """

    def deco(fn):
        OPS.setdefault(name, fn)
        fn.op_name = name
        return fn

    return deco


# Set by static.program.enable_static_mode (avoids an import cycle and
# keeps the dynamic-mode hot path to one None check).
_static_state = None


def op_call(op_name: str, default_fn, *args, **kwargs):
    """Registry-routed op execution (the analog of the reference's kernel
    dispatch, phi/core/kernel_factory.h:58 KernelFactory::SelectKernel).

    Registers ``default_fn`` as the op's default body and resolves the
    body from ``OPS`` at CALL time, so ``override_kernel(op_name, fn)``
    reaches this op — eagerly, under jit tracing, and through autograd —
    with the full call signature (arrays positional, settings as kwargs).

    When an OVERRIDDEN body raises NotImplementedError and
    ``FLAGS_enable_api_kernel_fallback`` is on (default, the reference's
    kernel-fallback behavior), the call retries with the default body.
    """
    transient = kwargs.pop("_transient", False)
    body = OPS.get(op_name)
    if body is None:
        if transient:
            # per-call-site closures (bounded while_loop): resolve
            # overrides by family name but never register the closure —
            # a registry entry would pin the FIRST call's cond/body for
            # every later loop sharing the name (and leak them)
            body = default_fn
        else:
            OPS[op_name] = body = default_fn
    if _static_state is not None and _static_state.static_mode:
        # static-graph build (paddle.enable_static): ops over symbolic
        # Variables record into the current Program instead of executing
        from ..static.program import maybe_record, _NOT_RECORDED
        rec = maybe_record(op_name, body, default_fn, args, kwargs)
        if rec is not _NOT_RECORDED:
            return rec
    try:
        return eager_apply(op_name, body, args, kwargs)
    except NotImplementedError:
        if body is not default_fn \
                and GLOBAL_FLAGS.get("enable_api_kernel_fallback"):
            return eager_apply(op_name, default_fn, args, kwargs)
        raise


def override_kernel(name: str, fn):
    """Replace an op's body (e.g. with a Pallas kernel). Returns the old
    body. The replacement must accept the op's registered signature
    (``OPS[name]`` shows the default body)."""
    old = OPS.get(name)
    OPS[name] = fn
    return old


__all__ = ["primitive", "eager_apply", "op_body", "op_call",
           "override_kernel", "OPS"]
