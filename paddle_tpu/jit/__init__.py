"""paddle_tpu.jit — the compiled execution path.

Analog of the reference's jit stack (python/paddle/jit/api.py:197 to_static;
SOT bytecode capture jit/sot/; CINN compilation). On this stack the whole
pipeline collapses: the eager engine already executes jnp ops on ``._data``
arrays, so *tracing the eager code itself* under ``jax.jit`` captures
forward, tape-backward, optimizer update, buffer mutations, and RNG into a
single XLA computation — the role the reference needs SOT + PIR + CINN for.

- ``to_static(layer_or_fn)``: compiled forward with buffer-mutation capture
  and per-(shapes, training-flag) executable cache (the reference's program
  cache, paddle/fluid/framework/op_registry + executable cache).
- ``TrainStep(model, loss_fn, optimizer)``: one fused step — forward + loss +
  backward + optimizer — jit-compiled, params/optimizer state donated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import autograd as _ag
from ..core import random as _rng
from ..core.tensor import Tensor
from ..profiler import phases
from ..profiler.spans import span


def _collect_state(layer):
    """All tensors whose values a Layer's forward may read or write."""
    params = dict(layer.named_parameters())
    buffers = dict(layer.named_buffers())
    return params, buffers


class _Installed:
    """Temporarily swap Tensor._data for traced arrays, restore on exit."""

    def __init__(self, tensors: dict):
        self.tensors = tensors

    def __enter__(self):
        self.saved = {k: t._data for k, t in self.tensors.items()}
        return self

    def install(self, arrays: dict):
        for k, t in self.tensors.items():
            t._data = arrays[k]

    def current(self):
        return {k: t._data for k, t in self.tensors.items()}

    def __exit__(self, *exc):
        for k, t in self.tensors.items():
            t._data = self.saved[k]
        return False


def _tree_to_arrays(tree):
    return jax.tree.map(lambda x: x._data if isinstance(x, Tensor) else x, tree,
                        is_leaf=lambda x: isinstance(x, Tensor))


def _tree_to_tensors(tree):
    return jax.tree.map(
        lambda x: Tensor(x) if isinstance(x, (jax.Array,)) else x, tree)


class StaticFunction:
    """Compiled forward wrapper (reference: StaticFunction in
    python/paddle/jit/dy2static/program_translator.py).

    Guard/fallback semantics (the SOT graph-break analog, reference
    jit/sot/translate.py): the cache key guards on every input's
    shape+dtype and every non-tensor argument's value, so a changed Python
    argument or shape re-traces rather than reusing a stale program. When
    the traced function turns out to need concrete tensor VALUES for Python
    control flow (a data-dependent ``if``/``while``), tracing raises — the
    wrapper then graph-breaks: it marks the signature and permanently runs
    it eagerly (one warning), instead of silently baking a single branch.
    """

    def __init__(self, fn, layer=None):
        # SOT loop capture (round-5): safe tensor-dependent `while` loops
        # are source-rewritten to compile ONCE via lax.while_loop instead
        # of one specialization per trip count (loop_rewrite.py)
        from .loop_rewrite import rewrite_loops
        fn = rewrite_loops(fn)
        self._fn = fn
        self._layer = layer
        self._cache = {}
        self._graph_broken = set()
        # SOT-lite value guards (core/branch_guards.py): per signature, a
        # dict of branch-decision-vector -> compiled specialization
        self._guarded = {}
        functools.update_wrapper(self, fn)

    def _key(self, flat_args):
        sig = tuple(
            (a.shape, str(a.dtype)) if hasattr(a, "shape") else ("py", repr(a))
            for a in flat_args)
        training = self._layer.training if self._layer is not None else None
        return (sig, training)

    def __call__(self, *args, **kwargs):
        layer = self._layer
        params, buffers = _collect_state(layer) if layer is not None else ({}, {})
        state = {**{f"p:{k}": v for k, v in params.items()},
                 **{f"b:{k}": v for k, v in buffers.items()}}
        flat_in, in_tree = jax.tree.flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        arr_in = [x._data if isinstance(x, Tensor) else x for x in flat_in]
        tensor_pos = [i for i, x in enumerate(flat_in) if isinstance(x, Tensor)]
        key = self._key(arr_in)
        if key in self._graph_broken:
            return self._fn(*args, **kwargs)
        if key in self._guarded:
            return self._run_guarded(key, state, flat_in, in_tree,
                                     tensor_pos, arr_in, args, kwargs)

        if key not in self._cache:
            self._cache[key] = self._build_pure(state, flat_in, in_tree,
                                                tensor_pos)
            self._maybe_dump_ir(key, state, arr_in, tensor_pos)

        state_arrays = {k: t._data for k, t in state.items()}
        dyn = [arr_in[i] for i in tensor_pos]
        try:
            out_arrays, new_state = self._cache[key](
                state_arrays, _rng.next_key(), *dyn)
        except (jax.errors.TracerBoolConversionError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.TracerArrayConversionError):
            del self._cache[key]
            # SOT-lite: tensor-dependent `if` — record the branch-decision
            # vector eagerly, then compile a per-branch specialization with
            # value guards (reference capability: jit/sot re-traces per
            # guarded branch, translate.py:106). Non-bool concretizations
            # (int shapes etc.) still graph-break.
            from ..core import branch_guards as _bg
            with _bg.record() as rec:
                out = self._fn(*args, **kwargs)
            decisions = rec.decisions
            if not decisions:
                import warnings
                warnings.warn(
                    f"jit.to_static({getattr(self._fn, '__name__', self._fn)}): "
                    "tensor-dependent Python control flow cannot be "
                    "captured — falling back to eager for this input "
                    "signature (use paddle.where or static shapes)",
                    stacklevel=2)
                self._graph_broken.add(key)
                return out
            self._warn_loop_sites(rec.loop_sites)
            from collections import OrderedDict
            entry = {"specs": OrderedDict(), "last": decisions}
            entry["specs"][decisions] = self._build_pure(
                state, flat_in, in_tree, tensor_pos, decisions)
            self._guarded[key] = entry
            return out    # eager result this call; compiled from the next
        # commit buffer mutations (running stats etc.); params are read-only here
        for k, t in state.items():
            if k.startswith("b:"):
                t._data = new_state[k]
        return _tree_to_tensors(out_arrays)

    def _build_pure(self, state, flat_in, in_tree, tensor_pos,
                    decisions=None):
        """jit the functionalized eager call. With ``decisions``, the trace
        replays that branch-decision vector at every tensor bool and the
        condition values ride along as guard outputs."""
        from ..core import branch_guards as _bg

        installer = _Installed(state)
        # template keeps only non-tensor leaves; tensor slots are filled
        # from dyn_args each call (so no input batch is pinned in HBM)
        template = [None if isinstance(x, Tensor) else x for x in flat_in]

        def pure(state_arrays, rng_key, *dyn_args):
            with installer:
                installer.install(state_arrays)
                with _rng.capture_rng(rng_key), _ag.no_grad():
                    vals = list(template)
                    for i, a in zip(tensor_pos, dyn_args):
                        vals[i] = a
                    a_args, a_kwargs = jax.tree.unflatten(in_tree, [
                        Tensor(v) if i in tensor_pos else v
                        for i, v in enumerate(vals)])
                    if decisions is None:
                        out = self._fn(*a_args, **a_kwargs)
                        conds = None
                    else:
                        with _bg.replay(decisions) as rp:
                            out = self._fn(*a_args, **a_kwargs)
                        conds = tuple(
                            jnp.reshape(jnp.asarray(c), ()).astype(bool)
                            for c in rp.conds)
                new_state = installer.current()
            out_arrays = jax.tree.map(
                lambda x: x._data if isinstance(x, Tensor) else x, out,
                is_leaf=lambda x: isinstance(x, Tensor))
            if decisions is None:
                return out_arrays, new_state
            return out_arrays, new_state, conds

        return jax.jit(pure)

    def _run_guarded(self, key, state, flat_in, in_tree, tensor_pos,
                     arr_in, args, kwargs):
        """Dispatch among branch specializations.

        Run the last-used specialization; its guard outputs are the
        condition values computed on the CURRENT inputs, so the first
        guard that disagrees with the specialization's decision vector
        reveals the true branch — dispatch to (or record+compile) the
        right specialization instead of permanent eager fallback.
        """
        from ..core import branch_guards as _bg

        entry = self._guarded[key]
        state_arrays = {k: t._data for k, t in state.items()}
        dyn = [arr_in[i] for i in tensor_pos]
        vec = entry["last"]
        tried = set()
        for _ in range(len(entry["specs"]) + 1):
            tried.add(vec)
            try:
                out_arrays, new_state, conds = entry["specs"][vec](
                    state_arrays, _rng.next_key(), *dyn)
            except _bg.GuardOverflow:
                # the branch STRUCTURE is input-dependent beyond value
                # specialization — drop the spec and re-record
                del entry["specs"][vec]
                break
            except (jax.errors.TracerBoolConversionError,
                    jax.errors.ConcretizationTypeError,
                    jax.errors.TracerIntegerConversionError,
                    jax.errors.TracerArrayConversionError):
                # a NON-bool concretization inside a guarded branch: value
                # guards cannot capture it — graph-break like the plain
                # path (the removed-fallback regression)
                import warnings
                warnings.warn(
                    f"jit.to_static({getattr(self._fn, '__name__', self._fn)}): "
                    "tensor-dependent Python control flow cannot be "
                    "captured — falling back to eager for this input "
                    "signature (use paddle.where or static shapes)",
                    stacklevel=2)
                self._graph_broken.add(key)
                del self._guarded[key]
                return self._fn(*args, **kwargs)
            observed = tuple(bool(c) for c in conds)
            if observed == vec:
                entry["last"] = vec
                if hasattr(entry["specs"], "move_to_end"):
                    entry["specs"].move_to_end(vec)   # LRU recency
                for k, t in state.items():
                    if k.startswith("b:"):
                        t._data = new_state[k]
                return _tree_to_tensors(out_arrays)
            # first divergent guard is computed on the shared prefix path,
            # so its value is the true decision
            k_div = next((i for i, (o, v) in enumerate(zip(observed, vec))
                          if o != v), None)
            if k_div is None:
                break    # lengths diverged: structure mismatch, re-record
            prefix = vec[:k_div] + (observed[k_div],)
            matches = [v for v in entry["specs"]
                       if v[:k_div + 1] == prefix and v not in tried]
            if matches:
                vec = matches[0]   # refine along any consistent candidate
                continue
            break
        # unknown branch path: eager run records it; compile for next time
        with _bg.record() as rec:
            out = self._fn(*args, **kwargs)
        decisions = rec.decisions
        if decisions and decisions not in entry["specs"]:
            self._warn_loop_sites(rec.loop_sites)
            entry["specs"][decisions] = self._build_pure(
                state, flat_in, in_tree, tensor_pos, decisions)
            # bounded specialization cache with LRU eviction (round-3
            # verdict item 5: k independent branches can demand 2^k specs;
            # a data-dependent Python loop demands one per trip count)
            from ..core.flags import GLOBAL_FLAGS
            bound = max(int(GLOBAL_FLAGS.get(
                "sot_specialization_cache_size")), 1)
            while len(entry["specs"]) > bound:
                entry["specs"].popitem(last=False)
        if decisions:
            entry["last"] = decisions
        return out

    def _maybe_dump_ir(self, key, state, arr_in, tensor_pos):
        """FLAGS_logging_pir_py_code_dir: dump the jaxpr text of each
        newly-compiled specialization (the reference's PIR py-code dump,
        logging_utils; jaxpr/StableHLO is the IR on this stack)."""
        from ..core.flags import GLOBAL_FLAGS
        out_dir = GLOBAL_FLAGS.get("logging_pir_py_code_dir")
        if not out_dir:
            return
        try:
            import os
            os.makedirs(out_dir, exist_ok=True)
            state_arrays = {k: t._data for k, t in state.items()}
            dyn = [arr_in[i] for i in tensor_pos]
            # constant key: a debug dump must not advance the global RNG
            # stream (that would change model numerics when the flag is on)
            dump_key = jax.random.PRNGKey(0)
            jaxpr = jax.make_jaxpr(self._cache[key]._fun
                                   if hasattr(self._cache[key], "_fun")
                                   else self._cache[key])(
                state_arrays, dump_key, *dyn)
            name = getattr(self._fn, "__name__", "fn")
            path = os.path.join(
                out_dir, f"{name}_{abs(hash(key)) & 0xFFFFFFFF:08x}.jaxpr")
            # jaxpr text renders constants as names only; append a consts
            # section so the dump is self-contained, with
            # FLAGS_logging_pir_py_code_int_tensor_element_limit bounding
            # how many elements each constant renders.
            # FLAGS_logging_trunc_pir_py_code caps the dump file itself.
            import numpy as _np
            limit = int(GLOBAL_FLAGS.get(
                "logging_pir_py_code_int_tensor_element_limit"))
            text = str(jaxpr)
            if getattr(jaxpr, "consts", None):
                lines = ["", "consts:"]
                for i, c in enumerate(jaxpr.consts):
                    a = _np.asarray(c)
                    body = _np.array2string(
                        a, threshold=max(limit, 1),
                        edgeitems=max(limit // 2, 1))
                    lines.append(f"  c{i}: {a.dtype}{list(a.shape)} = {body}")
                text += "\n".join(lines) + "\n"
            if GLOBAL_FLAGS.get("logging_trunc_pir_py_code") \
                    and len(text) > 65536:
                text = text[:65536] + "\n... [truncated by " \
                    "FLAGS_logging_trunc_pir_py_code]\n"
            with open(path, "w") as f:
                f.write(text)
        except Exception:
            pass  # a debug dump must never break the compile path

    def _warn_loop_sites(self, loop_sites):
        """One-time hint when a capture shows a tensor-dependent LOOP:
        value guards compile one specialization per trip count; the O(1)
        compile path is paddle.static.nn.while_loop (lax.while_loop)."""
        if not loop_sites:
            return
        warned = getattr(self, "_loop_warned", set())
        self._loop_warned = warned
        for site, n in loop_sites.items():
            if site in warned or n < 4:
                continue
            warned.add(site)
            from ..core.vlog import vlog
            vlog(0, f"to_static: tensor-dependent loop at {site[0]}:"
                    f"{site[1]} ({n} iterations) compiles one "
                    "specialization per trip count; rewrite with "
                    "paddle.static.nn.while_loop to compile once",
                 component="jit")

    @property
    def forward(self):
        return self


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph=True, **kwargs):
    """``paddle.jit.to_static`` analog (reference: python/paddle/jit/api.py:197)."""

    def deco(fn):
        from ..nn import Layer
        if isinstance(fn, Layer):
            layer = fn
            static = StaticFunction(layer.forward, layer)
            layer.forward = static
            return layer
        return StaticFunction(fn, None)

    if function is None:
        return deco
    return deco(function)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


class TrainStep:
    """Fused compiled training step.

    Traces the *eager* engine — forward, tape backward, optimizer — into one
    XLA executable. Parameter and optimizer-state buffers are donated so
    updates are in-place in HBM (the reference needs fused multi-tensor
    kernels + interpreter scheduling for the same effect, SURVEY.md §3.3).

    ``accumulate_steps=K`` runs micro-batch gradient accumulation INSIDE
    the compiled step: the batch splits into K equal micro-batches along
    axis 0 and a ``lax.scan`` threads one gradient accumulator a leaf
    through K forward+backward replays (the body is traced
    once — HLO stays O(1) in K), then applies ONE optimizer update from
    the mean gradients. The accumulators never leave the device and the
    host still issues exactly one dispatch per optimizer step, so a K×
    effective batch fits in the activation memory of a batch/K step.
    Numerically the update equals a single K×-batch step for mean-shaped
    losses (micro means averaged over K).

    ``remat_policy`` pins the activation rematerialization policy
    ('none' / 'dots_saveable' / 'full', see FLAGS_remat_policy) for this
    step's traces; None defers to the flag.

    Usage::
        step = TrainStep(model, lambda x, y: F.cross_entropy(model(x), y), opt)
        loss = step(x_batch, y_batch)
    """

    def __init__(self, model, loss_fn, optimizer, accumulate_steps=1,
                 remat_policy=None, sharding=None, capture_hlo=False):
        from ..nn.scan_stack import REMAT_POLICIES
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.accumulate_steps = int(accumulate_steps)
        if self.accumulate_steps < 1:
            raise ValueError("accumulate_steps must be >= 1")
        if remat_policy is not None and remat_policy not in REMAT_POLICIES:
            raise ValueError(
                f"remat_policy must be one of {REMAT_POLICIES} or None, "
                f"got {remat_policy!r}")
        self.remat_policy = remat_policy
        # GSPMD partitioning (distributed/gspmd.py): DP/TP/ZeRO as
        # NamedSharding annotations over one (data, model) mesh, applied
        # as in/out_shardings of THIS step's one jax.jit — an explicit
        # ShardingConfig pins the regime, None defers to FLAGS_gspmd.
        from ..distributed import gspmd as _gspmd
        if sharding is not None and not isinstance(
                sharding, _gspmd.ShardingConfig):
            sharding = _gspmd.ShardingConfig.parse(str(sharding))
        self.sharding = sharding
        #: HLO forensics of the most recent forensics-captured compile:
        #: the full module text + its collective-op counts. Captured for
        #: every GSPMD-annotated compile (the collective-mix gates need
        #: it) and, with ``capture_hlo=True``, for unsharded compiles
        #: too (``chip_smoke.py`` and tests/test_hlo_forensics.py read
        #: it — one extra lower+compile per first call, so it stays
        #: opt-in). None until
        #: a captured specialization has been built.
        self.capture_hlo = bool(capture_hlo)
        self.last_hlo_text = None
        self.last_hlo_collectives = None
        # compile forensics: wall-ms of the most recent first-call
        # trace+lower+build, and the running total across re-specializes
        # (shape changes, flag flips). Read by tests/test_scan_layers.py.
        self.last_compile_ms = None
        self.compile_ms_total = 0.0
        self._cache = {}
        self._compiled_keys = set()
        # materialize optimizer state now so it traces as inputs
        params = [p for p in optimizer._parameter_list if not p.stop_gradient]
        self._params = {f"p{i}": p for i, p in enumerate(params)}
        # positional key -> model parameter name: the GSPMD rule table is
        # name-driven (q_proj/o_proj/embed/...), while the step's pytree
        # keys are positional. LayerStack leaves keep their "stacked."
        # marker (the Parameter's own name, not the attribute path) so
        # the pp=K stage-slicing rule can recognize the [L, ...] layout.
        by_id = {}
        if hasattr(model, "named_parameters"):
            by_id = {id(p): ("stacked." + n
                             if str(getattr(p, "name", "")
                                    ).startswith("stacked.") else n)
                     for n, p in model.named_parameters()}
        self._param_names = {k: by_id.get(id(p), k)
                             for k, p in self._params.items()}

    def _opt_state_arrays(self):
        """The optimizer's state as the step's donated input: one array
        a leaf and state name, keyed ``"{param key}.{name}"``."""
        out = {}
        for i, p in self._params.items():
            st = self.optimizer._state.get(id(p))
            if st:
                for k, v in st.items():
                    out[f"{i}.{k}"] = v
        return out

    def _install_opt_state(self, arrays):
        state = {}
        for key, v in arrays.items():
            i, name = key.split(".", 1)
            state.setdefault(i, {})[name] = v
        for i, st in state.items():
            self.optimizer._state[id(self._params[i])] = st

    def __call__(self, *batch):
        """One optimizer step. The call is the span ``train.step`` (the
        device works on past its end), cut into ``train.gather``
        (everything before the jitted call) and ``train.dispatch``, or
        ``train.compile`` on a specialization's first call
        (profiler/spans.py)."""
        with span("train.step") as sp:
            return self._step(sp, batch)

    def _step(self, sp, batch):
        sp.phase("train.gather")
        from ..core.flags import GLOBAL_FLAGS
        from ..io.prefetch import PIPELINE_METRICS
        from ..nn.scan_stack import remat_policy_scope, effective_remat_policy
        _, buffers = _collect_state(self.model)
        for b in batch:
            if isinstance(b, Tensor) and getattr(b, "_donated", False):
                raise RuntimeError(
                    "TrainStep received a batch tensor whose buffer was "
                    "already donated to a previous compiled step. Staged "
                    "batches (DataLoader(use_buffer_reader=True)) are "
                    "single-use on TPU; to reuse a batch across steps, "
                    "pass your own tensor or set use_buffer_reader=False.")
        batch_arrays = tuple(b._data if isinstance(b, Tensor) else jnp.asarray(b)
                             for b in batch)
        K = self.accumulate_steps
        if K > 1:
            if buffers:
                raise RuntimeError(
                    "TrainStep(accumulate_steps>1) cannot scan a model "
                    "with registered buffers: per-micro-batch buffer "
                    "mutations cannot be committed from a scan body. Use "
                    "accumulate_steps=1 (or an outer accumulation loop) "
                    "for buffer-mutating models.")
            if any(not a.shape or a.shape[0] % K for a in batch_arrays):
                # ragged tail batch (drop_last=False loaders): process it
                # as ONE micro-batch — the mean-grad update is identical
                # to accumulating it in smaller pieces, and the odd shape
                # re-specializes the step anyway. Warn once so a loader
                # that NEVER divides doesn't silently disable
                # accumulation for the whole run.
                if not getattr(self, "_warned_ragged", False):
                    import warnings
                    self._warned_ragged = True
                    warnings.warn(
                        f"TrainStep(accumulate_steps={K}): batch axis 0 "
                        f"{[tuple(a.shape) for a in batch_arrays]} is not "
                        f"divisible by {K}; running this batch without "
                        "accumulation (expected for a drop_last=False "
                        "tail batch — if every batch hits this, fix the "
                        "batch size)", stacklevel=2)
                K = 1
        check_finite = bool(GLOBAL_FLAGS.get("check_nan_inf"))
        # remat enters the cache key: the policy is baked into the traced
        # program (jax.checkpoint over the scanned body), so a flag flip
        # must re-specialize rather than reuse a stale executable. The
        # explicit TrainStep override pins a scope for the trace; without
        # one the model resolves the flag (and its own config.remat).
        remat = self.remat_policy or effective_remat_policy()
        from contextlib import nullcontext
        policy_ctx = (remat_policy_scope(self.remat_policy)
                      if self.remat_policy else nullcontext())
        # Staged-batch donation: batches the prefetch pipeline put on the
        # device (io/prefetch.py marks them _staged_h2d) are consumed
        # exactly once, so their buffers can be given back to XLA — the
        # step reuses the HBM instead of allocating fresh activations next
        # to a dead input copy. A caller-owned tensor (e.g. the bench
        # reusing one batch) is never donated.
        donate_batch = bool(batch) and jax.default_backend() != "cpu" and \
            all(isinstance(b, Tensor) and getattr(b, "_staged_h2d", False)
                for b in batch)
        from ..distributed import gspmd as _gspmd
        shard_cfg = self.sharding or _gspmd.config_from_flags()
        if shard_cfg is not None:
            shard_cfg = shard_cfg.resolve()
        pipe_M = 0
        if shard_cfg is not None and shard_cfg.pipe > 1:
            pipe_M = int(GLOBAL_FLAGS.get("pipeline_microbatches")) \
                or shard_cfg.pipe
            self._validate_pipeline(shard_cfg, batch_arrays, pipe_M)
        cfg_key = None if shard_cfg is None else \
            (shard_cfg.data, shard_cfg.model, shard_cfg.zero,
             shard_cfg.pipe, pipe_M)
        key = tuple((a.shape, str(a.dtype)) for a in batch_arrays) \
            + (check_finite, donate_batch, K, remat, cfg_key)

        # An eager ``opt.step()`` since the last call left the state flat
        # in the optimizer's engine: take it down to the leaves, where this
        # step keeps it (one attribute read when there is nothing to take).
        self.optimizer._flat_state_to_params()
        if key not in self._cache:
            param_t = dict(self._params)
            buffer_t = {f"b:{k}": v for k, v in buffers.items()}
            opt = self.optimizer
            model = self.model
            loss_fn = self.loss_fn
            step_holder = {}
            mesh = None
            batch_sh = None
            place = None
            if shard_cfg is not None:
                mesh = _gspmd.build_mesh(shard_cfg)
                self._mesh = mesh
                batch_sh = tuple(_gspmd.batch_sharding(a, mesh)
                                 for a in batch_arrays)
                p_sh = _gspmd.named_param_shardings(
                    {k: (self._param_names[k], tuple(p._data.shape))
                     for k, p in self._params.items()}, mesh)

                def place(k, prm, st):
                    prm._data = jax.device_put(prm._data, p_sh[k])
                    sh = _gspmd.opt_state_shardings(
                        {f"{k}.{n}": v for n, v in st.items()}, p_sh,
                        mesh, zero=shard_cfg.zero)
                    for n, v in st.items():
                        st[n] = jax.device_put(v, sh[f"{k}.{n}"])
            # Under a mesh the state goes where the step keeps it BEFORE
            # the first call, a leaf and its moments at a time. A freshly
            # built model sits whole on device 0; handed to the jit as it
            # is, the first call holds that original, its resharded
            # (donated) copy and the program's temporaries together — at
            # real sizes that does not fit the device the model was built
            # on. Nor would every moment, created whole there at once.
            self._prime_state(place)

            def pure_step(param_arrays, opt_arrays, buffer_arrays, step_i, lr, rng, *b_arrays):
                if mesh is not None:
                    # pin the data-parallel batch split inside the traced
                    # program too (in_shardings place the inputs; the
                    # constraint stops the partitioner from re-replicating
                    # the batch into the forward)
                    b_arrays = tuple(
                        jax.lax.with_sharding_constraint(b, sh)
                        for b, sh in zip(b_arrays, batch_sh))
                inst_p = _Installed(param_t)
                inst_b = _Installed(buffer_t)
                saved_state = {pid: dict(st) for pid, st in opt._state.items()}
                saved_step, saved_lr = opt._step_count, opt._lr
                saved_grads = {k: p.grad for k, p in param_t.items()}
                try:
                    with inst_p, inst_b, _rng.capture_rng(rng):
                        inst_p.install(param_arrays)
                        inst_b.install(buffer_arrays)
                        self._install_opt_state(opt_arrays)
                        opt._step_count = step_i
                        opt._lr = lr
                        for p in param_t.values():
                            p.grad = None
                        if K == 1:
                            batch_tensors = [Tensor(a) for a in b_arrays]
                            loss = loss_fn(*batch_tensors)
                            loss.backward()
                            loss_arr = loss._data
                        else:
                            loss_arr = self._accumulate_grads(
                                loss_fn, param_t, b_arrays, K, rng)
                        opt.step()
                        new_params = inst_p.current()
                        new_buffers = inst_b.current()
                        new_opt = self._opt_state_arrays()
                        if check_finite:
                            # compiled-path numerical sanitizer (reference:
                            # new_executor/nan_inf_utils.h under
                            # FLAGS_check_nan_inf): one fused all-finite
                            # reduction over loss + updated params, checked
                            # host-side — no per-op sync like the eager sweep
                            import jax.numpy as _jnp
                            finite = _jnp.isfinite(loss_arr).all()
                            for v in new_params.values():
                                if _jnp.issubdtype(v.dtype, _jnp.inexact):
                                    finite &= _jnp.isfinite(v).all()
                            return new_params, new_opt, new_buffers, \
                                loss_arr, finite
                        return new_params, new_opt, new_buffers, loss_arr
                finally:
                    opt._state = saved_state
                    opt._step_count, opt._lr = saved_step, saved_lr
                    for k, p in param_t.items():
                        p.grad = saved_grads[k]

            donate = (0, 1) if jax.default_backend() != "cpu" else ()
            if donate_batch:
                # b_arrays start after the 6 fixed args of pure_step
                donate = donate + tuple(range(6, 6 + len(batch_arrays)))
            jit_kw = {}
            if mesh is not None:
                # GSPMD: the regime IS this annotation set — params by
                # the name-driven rule table, every leaf's optimizer
                # state where its param lives (split over the data axis
                # besides under ZeRO), batch on data, scalars/rng/buffers
                # replicated. Identical in/out shardings keep the
                # param/opt donation valid on TPU.
                o_sh = _gspmd.opt_state_shardings(
                    self._opt_state_arrays(), p_sh, mesh,
                    zero=shard_cfg.zero)
                b_sh = {k: _gspmd.replicated(mesh) for k in buffer_t}
                rep = _gspmd.replicated(mesh)
                out_sh = (p_sh, o_sh, b_sh, rep)
                if check_finite:
                    out_sh = out_sh + (rep,)
                jit_kw = dict(
                    in_shardings=(p_sh, o_sh, b_sh, rep, rep, rep)
                    + batch_sh,
                    out_shardings=out_sh)
            self._cache[key] = jax.jit(pure_step, donate_argnums=donate,
                                       **jit_kw)

        param_arrays = {k: p._data for k, p in self._params.items()}
        opt_arrays = self._opt_state_arrays()
        buffer_arrays = {f"b:{k}": v._data for k, v in buffers.items()}
        lr = self.optimizer.get_lr()
        step_in = self.optimizer._step_count  # inside-trace step() adds 1
        rng_key = _rng.next_key()
        eager_loss = None
        if GLOBAL_FLAGS.get("enable_cinn_accuracy_check") \
                and key not in getattr(self, "_accuracy_checked", set()):
            # FLAGS_enable_cinn_accuracy_check (reference flags.cc): once
            # per compiled specialization, recompute the loss through the
            # EAGER engine on the same params + rng key and compare within
            # the accuracy_check_* tolerances — catches a compiled-path
            # lowering that silently diverges from eager. Runs BEFORE the
            # compiled call: on TPU the compiled step donates the param /
            # opt-state buffers, so reading them afterwards would hit
            # deleted arrays. Buffer bindings mutated by the eager forward
            # (e.g. running stats) are restored — the compiled step's
            # updates are the ones that count.
            self._accuracy_checked = getattr(self, "_accuracy_checked", set())
            self._accuracy_checked.add(key)
            saved_buf = {k: t._data for k, t in buffers.items()}
            try:
                with _rng.capture_rng(rng_key):
                    eager_loss = float(self.loss_fn(*batch).numpy())
            finally:
                for k, t in buffers.items():
                    t._data = saved_buf[k]
        PIPELINE_METRICS.record_dispatch()
        first_run = key not in self._compiled_keys
        args = (param_arrays, opt_arrays, buffer_arrays,
                jnp.asarray(step_in, jnp.int32),
                jnp.asarray(lr, jnp.float32), rng_key, *batch_arrays)
        if first_run:
            # first call of this specialization = trace + lower + build:
            # record a `compile` span on the profiler timeline so a
            # recompile (shape change, remat/flag flip) is visible next
            # to the pipeline gauges instead of reading as one slow step.
            from ..profiler import compile_event
            sp.phase(None)
            # pp>1: LayerStack.forward switches to the stage-sliced
            # pipelined scan while this scope is bound around the trace
            pipe_ctx = (_gspmd.pipeline_scope(
                self._mesh, shard_cfg.pipe, pipe_M)
                if shard_cfg is not None and shard_cfg.pipe > 1
                else nullcontext())
            # the launch's shapes, read before the call donates them
            specs = phases.launch_specs(args)
            with policy_ctx, pipe_ctx, \
                    compile_event("train.compile") as ev:
                out = self._cache[key](*args)
            # ONE handle on the compiled step, taken after its first
            # call: lowering over the launch's own shapes hits JAX's
            # in-memory caches (no second trace, no second compile).
            # profiler/phases.py keeps it for device time by phase; a
            # sharded (or capture_hlo) specialization also reads its
            # text for HLO forensics (tests/test_gspmd.py,
            # tests/test_pipeline_parallel.py; jit/hlo_forensics.py
            # fusion stats over it in tests/test_hlo_forensics.py).
            with policy_ctx, pipe_ctx, \
                    span("train.register"):         # what it costs, once
                compiled = self._cache[key].lower(*specs).compile()
            phases.register(
                "train.step" if not self._compiled_keys
                else f"train.step:{len(self._compiled_keys)}", compiled)
            if shard_cfg is not None or self.capture_hlo:
                self.last_hlo_text = compiled.as_text()
                self.last_hlo_collectives = \
                    _gspmd.collective_counts(self.last_hlo_text)
            self._compiled_keys.add(key)
            self.last_compile_ms = ev.ms
            self.compile_ms_total += ev.ms
        else:
            sp.phase("train.dispatch")
            with policy_ctx:
                out = self._cache[key](*args)
            sp.phase(None)
        if donate_batch:
            for b in batch:
                # buffer handed to XLA: mark so a reuse raises our error
                # above instead of jax's opaque "Array has been deleted"
                b._staged_h2d = False
                b._donated = True
        if check_finite:
            new_p, new_o, new_b, loss, finite = out
            if not bool(finite):
                raise FloatingPointError(
                    f"NaN/Inf detected in compiled train step "
                    f"{self.optimizer._step_count} (FLAGS_check_nan_inf)")
        else:
            new_p, new_o, new_b, loss = out
        if eager_loss is not None:
            compiled_loss = float(jnp.asarray(loss))
            # no `or`-defaults: an explicit 0 tolerance must stay 0
            rtol = float(GLOBAL_FLAGS.get("accuracy_check_rtol_fp32"))
            atol = float(GLOBAL_FLAGS.get("accuracy_check_atol_fp32"))
            self.last_accuracy_check = {
                "eager": eager_loss, "compiled": compiled_loss}
            if abs(eager_loss - compiled_loss) > atol + rtol * abs(eager_loss):
                raise FloatingPointError(
                    f"compiled/eager loss mismatch (FLAGS_enable_cinn_"
                    f"accuracy_check): eager {eager_loss} vs compiled "
                    f"{compiled_loss} (rtol {rtol}, atol {atol})")
        self.optimizer._step_count += 1
        for k, p in self._params.items():
            p._data = new_p[k]
        self._install_opt_state(new_o)
        for k, t in buffers.items():
            t._data = new_b[f"b:{k}"]
        return Tensor(loss)

    def _accumulate_grads(self, loss_fn, param_t, b_arrays, K, rng):
        """Micro-batch gradient accumulation inside the traced step.

        Splits each batch array into K equal micro-batches along axis 0
        and ``lax.scan``s one forward+backward per micro-batch — the tape
        replay is traced ONCE, so HLO stays O(1) in K. The carry is one
        gradient accumulator a leaf, in the leaf's own shape and (under a
        mesh) sharding, plus the running loss; XLA double-buffers the
        carry in place across iterations, so the accumulators never leave
        the device. On exit the mean grads land on ``p.grad`` and the
        caller runs ONE optimizer update — host dispatches per optimizer
        step are unchanged from K=1.

        Participation mirrors the K=1 path: an abstract probe
        (``jax.eval_shape`` of one micro-batch's forward+backward, no
        FLOPs) discovers which params actually receive a gradient and
        with what dtype; non-participating params keep ``grad=None`` so
        the optimizer skips them exactly like a single K×-batch step
        would (no fabricated zero grads feeding weight decay / moments).
        Each micro-batch re-seeds the captured RNG stream with its scan
        index so stateful randomness (dropout) would not replay one
        traced key K times.
        """
        order = [(k, p) for k, p in param_t.items()
                 if jnp.issubdtype(jnp.result_type(p._data), jnp.inexact)]
        micro = tuple(
            a.reshape((K, a.shape[0] // K) + tuple(a.shape[1:]))
            for a in b_arrays)

        def _probe(mbs):
            for _, p in order:
                p.grad = None
            try:
                with _rng.capture_rng(jax.random.fold_in(rng, 0)):
                    loss = loss_fn(*[Tensor(a) for a in mbs])
                    loss.backward()
                return {name: p.grad._data for name, p in order
                        if p.grad is not None}
            finally:
                for _, p in order:
                    p.grad = None

        grad_shapes = jax.eval_shape(
            _probe, tuple(jax.ShapeDtypeStruct(m.shape[1:], m.dtype)
                          for m in micro))
        # params that never receive a grad are absent: optimizer skips them
        init = ({name: jnp.zeros(aval.shape, aval.dtype)
                 for name, aval in grad_shapes.items()},
                jnp.zeros((), jnp.float32))

        def body(carry, xs):
            acc, loss_acc = carry
            idx, mbs = xs[0], xs[1:]
            for _, p in order:
                p.grad = None
            with _rng.capture_rng(jax.random.fold_in(rng, idx)):
                loss = loss_fn(*[Tensor(a) for a in mbs])
                loss.backward()
            new_acc = {}
            for name, a in acc.items():
                grad = param_t[name].grad
                new_acc[name] = a if grad is None \
                    else a + grad._data.astype(a.dtype)
            for _, p in order:
                p.grad = None
            return (new_acc, loss_acc + loss._data.astype(jnp.float32)), None

        (acc, loss_sum), _ = jax.lax.scan(
            body, init, (jnp.arange(K),) + micro)
        for name, a in acc.items():
            param_t[name].grad = Tensor(a / K, stop_gradient=True)
        return loss_sum / K

    def _validate_pipeline(self, shard_cfg, batch_arrays, pipe_M):
        """pp=K preconditions, checked before the cache key so a bad
        preset fails loudly instead of replicating silently: K must
        divide both the device count left after dp x tp AND the model's
        scan-stacked layer count; the microbatch count M must divide
        the batch dim."""
        pipe = shard_cfg.pipe
        n = len(jax.devices())
        per_pp = n // (shard_cfg.data * shard_cfg.model)
        stack_layers = sorted({
            int(p._data.shape[0]) for k, p in self._params.items()
            if "stacked." in self._param_names.get(k, "")
            and p._data.ndim >= 2})
        bad_stack = (not stack_layers
                     or any(l % pipe for l in stack_layers))
        if per_pp % pipe or bad_stack:
            layers = stack_layers[0] if stack_layers else 0
            raise ValueError(
                f"gspmd 'pp={pipe}': the pipeline degree must divide "
                f"both the device count after dp x tp "
                f"({per_pp} = {n} devices / dp={shard_cfg.data} / "
                f"tp={shard_cfg.model}) and the model's scan-stacked "
                f"layer count ({layers}; 0 = no LayerStack — enable "
                f"FLAGS_scan_layers); got pp={pipe}, {per_pp} devices, "
                f"{layers} layers")
        for a in batch_arrays:
            if a.ndim >= 1 and a.shape[0] % pipe_M:
                raise ValueError(
                    f"gspmd 'pp={pipe}': microbatch count M={pipe_M} "
                    f"(FLAGS_pipeline_microbatches, 0 = auto = pp) must "
                    f"divide the batch dim {a.shape[0]}")

    def _prime_state(self, place=None):
        """Create every leaf's optimizer state from ``_state_schema``
        ahead of tracing, so that it rides as donated inputs rather than
        baked constants; ``place(key, param, state)``, under a mesh, puts
        each leaf and its fresh state where the step keeps them before
        the next leaf's is made. Inside the compiled step the optimizer
        updates each leaf where it lies, in its own shape, layout and
        sharding (``Optimizer._apply``: traced gradients take the
        per-leaf loop); the flat buckets of ``optimizer/fused.py`` are
        the eager path's."""
        for k, p in self._params.items():
            st = self.optimizer._param_state(p)
            if place is not None:
                place(k, p, st)


def save(layer, path, input_spec=None, **config):
    """``paddle.jit.save`` analog: persist weights + (when exportable) the
    serialized compiled program via jax.export
    (reference: python/paddle/jit/api.py save → TranslatedLayer artifacts)."""
    from ..framework.io import save as fsave
    state = layer.state_dict() if hasattr(layer, "state_dict") else {}
    fsave({"state_dict": state, "format": "paddle_tpu.jit.v1"}, path + ".pdparams")


def load(path, **config):
    from ..framework.io import load as fload
    return fload(path + ".pdparams")


def enable_to_static(flag=True):
    pass


def ignore_module(modules):
    pass

from .save_load import save, load, InputSpec, TranslatedLayer  # noqa: F401,E402


def set_verbosity(level=0, also_to_stdout=False):
    """dy2static transcription verbosity (reference: jit/api.py
    set_verbosity -> TranslatorLogger): maps onto FLAGS_v so the vlog
    tier carries SOT diagnostics."""
    from ..core.flags import GLOBAL_FLAGS
    GLOBAL_FLAGS.set("v", int(level))


def set_code_level(level=100, also_to_stdout=False):
    """Dump transformed code up to ``level`` (reference: jit/api.py
    set_code_level). The SOT-lite pipeline has one transform stage, so any
    level >= 1 turns on specialization-dump logging via
    FLAGS_logging_pir_py_code_dir default '.' when unset."""
    from ..core.flags import GLOBAL_FLAGS
    if int(level) >= 1 and not GLOBAL_FLAGS.get("logging_pir_py_code_dir"):
        GLOBAL_FLAGS.set("logging_pir_py_code_dir", ".")
