"""Optimizer base + implementations.

Analog of the reference's python/paddle/optimizer/optimizer.py:128 plus the
per-algorithm files. Each optimizer's math is a pure jitted update function
``(param, grad, lr, *state) -> (new_param, *new_state)`` — XLA fuses the whole
update into one kernel per parameter (the role the reference's fused
multi-tensor CUDA kernels play, python/paddle/optimizer/fusion_utils.py).
The compiled training path (paddle_tpu.jit.TrainStep) calls the same pure
functions inside the jitted step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..regularizer import L1Decay
from .lr import LRScheduler


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        if self._parameter_list is None:
            raise ValueError("parameters must be provided in dygraph mode")
        # paddle: weight_decay may be float (L2Decay) or a *Decay object
        # (paddle.regularizer.L1Decay/L2Decay). L2 collapses to the coeff
        # the update kernels apply; L1 is applied to the grads in step().
        self._l1_decay = 0.0
        if isinstance(weight_decay, L1Decay):
            self._l1_decay = weight_decay._coeff
            self._weight_decay = 0.0
        else:
            self._weight_decay = getattr(weight_decay, "_coeff",
                                         weight_decay) or 0.0
        self._grad_clip = grad_clip
        self._state: dict[int, dict] = {}
        self._step_count = 0
        self._fused_engine = None  # lazy FusedOptimizerEngine (fused.py)

    # -- lr --
    def get_lr(self):
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        if isinstance(self._lr, (jax.Array, jax.core.Tracer)):
            return self._lr  # traced lr during jit capture (paddle_tpu.jit)
        return float(self._lr)

    def set_lr(self, value):
        self._lr = value

    def set_lr_scheduler(self, scheduler):
        self._lr = scheduler

    @property
    def _learning_rate(self):
        return self._lr

    # -- state --
    def _state_schema(self, p):
        """(name, init_fn) pairs for this optimizer's per-param state —
        the single source of truth used by both eager stepping and
        jit.TrainStep's state priming."""
        return []

    def _param_state(self, p):
        st = self._state.get(id(p))
        eng = self._fused_engine
        if eng is not None and eng.active \
                and (st is None or eng.state_dirty):
            # state lives in the engine's flat buckets; (re)materialize the
            # per-param views whenever the buffers advanced past them
            eng.sync_to_param_state()
            st = self._state.get(id(p))
        if st is None:
            st = {name: init(p._data) for name, init in self._state_schema(p)}
            self._state[id(p)] = st
        return st

    def state_dict(self):
        if self._fused_engine is not None and self._fused_engine.active:
            self._fused_engine.sync_to_param_state()
        out = {"step": self._step_count}
        for i, p in enumerate(self._parameter_list):
            st = self._state.get(id(p))
            if st:
                for k, v in st.items():
                    out[f"{p.name}.{k}"] = Tensor(v) if not isinstance(v, Tensor) else v
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def _flat_state_to_params(self):
        """Take the fused engine's live flat state down into per-param
        ``_state`` and drop its buckets: whoever steps next (the per-param
        loop, a compiled ``jit.TrainStep``, a rebuilt engine) starts from
        the leaves' own state. Nothing to do when no bucket is live."""
        eng = self._fused_engine
        if eng is not None and eng.active:
            eng.sync_to_param_state()
            eng.invalidate()

    def set_state_dict(self, state):
        # refresh per-param views first so keys ABSENT from `state` keep
        # their live values, then let the loaded keys overwrite; buckets
        # rebuild from the merged per-param state next eager step
        self._flat_state_to_params()
        self._step_count = state.get("step", 0)
        for p in self._parameter_list:
            st = {}
            prefix = f"{p.name}."
            for k, v in state.items():
                if isinstance(k, str) and k.startswith(prefix):
                    st[k[len(prefix):]] = v._data if isinstance(v, Tensor) else jnp.asarray(v)
            if st:
                self._state[id(p)] = st
        if "LR_Scheduler" in state and isinstance(self._lr, LRScheduler):
            self._lr.set_state_dict(state["LR_Scheduler"])

    # -- step --
    @no_grad()
    def step(self):
        self._step_count += 1
        params = [p for p in self._parameter_list
                  if p.grad is not None and not p.stop_gradient]
        grads = [p.grad._data for p in params]
        lr = self.get_lr()
        from ..profiler import phases
        with phases.phase("optimizer"):
            self._apply(params, grads, lr)

    def _apply(self, params, grads, lr):
        """Eager: one jitted update a dtype bucket over flat buffers
        (fused.py: O(#buckets) dispatches). Under a trace (the gradients
        are tracers: ``jit.TrainStep``, ``to_static``) there is one
        dispatch whatever runs here, so each leaf is updated where it
        lies, in its own shape, layout and sharding, by the per-param
        loop below; a flat bucket would be staging for nothing."""
        from .fused import FusedOptimizerEngine, _is_traced
        if params and not _is_traced(grads) and self._fused_enabled():
            if self._fused_engine is None:
                self._fused_engine = FusedOptimizerEngine(self)
            if self._fused_engine.step(params, grads, lr):
                return
        # handing back to the per-param loop (flag flipped off, params
        # became sharded): _apply_one must see the live flat state
        self._flat_state_to_params()
        from .fused import record_dispatch
        if self._grad_clip is not None:
            grads = self._grad_clip._clip_arrays(params, grads)
        if self._l1_decay:
            # after clipping, like the reference (apply_gradients appends
            # regularization ops after the clip ops) and like this repo's
            # L2 path (applied inside the update kernels post-clip)
            grads = [g + self._l1_decay * jnp.sign(p._data).astype(g.dtype)
                     for p, g in zip(params, grads)]
        for p, g in zip(params, grads):
            self._apply_one(p, g, lr)
            record_dispatch()

    def _apply_one(self, p, g, lr):
        raise NotImplementedError

    # -- fused multi-tensor path (fused.py) --
    def _fused_enabled(self):
        from ..core.flags import GLOBAL_FLAGS
        return bool(GLOBAL_FLAGS.get("fused_optimizer")) \
            and hasattr(self, "_fused_flat_update")

    def _prime_fused(self, params):
        """Build the fused engine's buckets over ``params`` ahead of the
        first eager step (tenancy/tune.py: a step whose grads land on a
        subset then masks their spans, never rebuilds). True when the
        fused path will serve ``step()``."""
        params = [p for p in params if not p.stop_gradient]
        if not (params and self._fused_enabled()):
            return False
        from .fused import FusedOptimizerEngine
        if self._fused_engine is None:
            self._fused_engine = FusedOptimizerEngine(self)
        return self._fused_engine.prime(params)

    def _fused_aux(self, params):
        """(static, arrays) bucket aux for the fused path: static python
        scalars plus per-ELEMENT f32 vectors broadcasting per-PARAM
        hyperparameters (AdamW's apply_decay_param_fun / lr_ratio hooks)
        over each param's span of the flat buffer."""
        return {}, {}

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..static.program import Variable, current_program, in_static_mode
        if in_static_mode() and isinstance(loss, Variable):
            # static-graph training (reference: Optimizer.minimize appends
            # backward + update ops to the Program): record the intent;
            # Executor.run replays forward then drives the eager tape
            # backward and applies this optimizer.
            current_program()._minimize = (self, loss)
            return None, None
        loss.backward()
        self.step()
        return None, None


# ---------------- SGD / Momentum ----------------

@jax.jit
def _sgd_update(p, g, lr, wd):
    g = g + wd * p
    return p - lr * g.astype(p.dtype)


class SGD(Optimizer):
    def _apply_one(self, p, g, lr):
        p._inplace_update(_sgd_update(p._data, g, lr, self._weight_decay))

    def _fused_flat_update(self, bucket, allow_kernel=True):
        """Flat-bucket mirror of ``_sgd_update`` (fused.py contract:
        ``(flat_p, flat_g, state, aux, lr, t) -> (new_flat_p, new_state)``,
        traced inside the bucket's single jitted dispatch)."""
        wd = self._weight_decay

        def upd(flat_p, flat_g, state, aux, lr, t):
            g = flat_g + wd * flat_p
            return flat_p - lr * g.astype(flat_p.dtype), state

        return upd


@functools.partial(jax.jit, static_argnums=(6,))
def _momentum_update(p, g, lr, vel, mu, wd, use_nesterov):
    g = g + wd * p
    v = mu * vel + g
    if use_nesterov:
        upd = g + mu * v
    else:
        upd = v
    return p - lr * upd.astype(p.dtype), v


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _state_schema(self, p):
        return [("velocity", jnp.zeros_like)]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        new_p, st["velocity"] = _momentum_update(
            p._data, g, lr, st["velocity"], self._momentum, self._weight_decay,
            self._nesterov)
        p._inplace_update(new_p)

    def _fused_flat_update(self, bucket, allow_kernel=True):
        mu, wd = self._momentum, self._weight_decay
        nesterov = self._nesterov

        def upd(flat_p, flat_g, state, aux, lr, t):
            g = flat_g + wd * flat_p
            v = mu * state["velocity"] + g
            u = g + mu * v if nesterov else v
            return flat_p - lr * u.astype(flat_p.dtype), {"velocity": v}

        return upd


# ---------------- Adam family ----------------

@functools.partial(jax.jit, static_argnums=(9, 10))
def _adam_update(p, g, lr, m, v, beta1, beta2, eps, t, decoupled_wd, wd=0.0):
    g = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    if not decoupled_wd and wd:
        g = g + wd * pf
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t)
    vhat = v / (1 - beta2 ** t)
    upd = mhat / (jnp.sqrt(vhat) + eps)
    if decoupled_wd and wd:
        upd = upd + wd * pf
    return (pf - lr * upd).astype(p.dtype), m, v


class Adam(Optimizer):
    _decoupled = False

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, name=None):
        """``lazy_mode`` (sparse-grad rows) and ``multi_precision`` are
        accepted for parity: moments are ALWAYS fp32 master state on this
        stack (the multi_precision=True behavior), and grads are dense."""
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _state_schema(self, p):
        return [("moment1", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("moment2", lambda d: jnp.zeros(d.shape, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        new_p, st["moment1"], st["moment2"] = _adam_update(
            p._data, g, lr, st["moment1"], st["moment2"], self._beta1, self._beta2,
            self._eps, self._step_count, self._decoupled, self._weight_decay)
        p._inplace_update(new_p)

    def _fused_flat_update(self, bucket, allow_kernel=True):
        """Flat-bucket mirror of ``_adam_update``, covering AdamW via
        ``_decoupled`` and the per-param wd / lr_ratio hooks via bucket aux
        vectors. Uniform-hyperparameter bf16/f32 buckets route through the
        Pallas fused-AdamW kernel on TPU (kernels/fused_adamw.py) — one
        VMEM pass over param + both moments."""
        beta1, beta2, eps = self._beta1, self._beta2, self._eps
        decoupled = self._decoupled
        wd = bucket.static.get("wd", self._weight_decay)
        wd_vec = "wd" in bucket.aux
        ratio = bucket.static.get("lr_ratio")
        ratio_vec = "lr_ratio" in bucket.aux
        has_wd = wd_vec or bool(wd)
        pdt = str(jnp.result_type(bucket.params[0]._data))
        kernel_ok = (allow_kernel and not wd_vec and not ratio_vec
                     and pdt in ("float32", "bfloat16"))

        def upd(flat_p, flat_g, state, aux, lr, t):
            lr_eff = lr if ratio is None else lr * ratio
            if kernel_ok:
                from ..kernels.fused_adamw import maybe_fused_adamw
                out = maybe_fused_adamw(
                    flat_p, flat_g, state["moment1"], state["moment2"],
                    lr_eff, t, beta1=beta1, beta2=beta2, eps=eps,
                    weight_decay=wd if has_wd else 0.0, decoupled=decoupled)
                if out is not None:
                    new_p, m, v = out
                    return new_p, {"moment1": m, "moment2": v}
            g = flat_g.astype(jnp.float32)
            pf = flat_p.astype(jnp.float32)
            w = aux["wd"] if wd_vec else wd
            if not decoupled and has_wd:
                g = g + w * pf
            m = beta1 * state["moment1"] + (1 - beta1) * g
            v = beta2 * state["moment2"] + (1 - beta2) * jnp.square(g)
            mhat = m / (1 - beta1 ** t)
            vhat = v / (1 - beta2 ** t)
            u = mhat / (jnp.sqrt(vhat) + eps)
            if decoupled and has_wd:
                u = u + w * pf
            if ratio_vec:
                lr_eff = lr * aux["lr_ratio"]
            return (pf - lr_eff * u).astype(flat_p.dtype), \
                {"moment1": m, "moment2": v}

        return upd


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py)."""
    _decoupled = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision, name)
        self._apply_decay_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _apply_one(self, p, g, lr):
        wd = self._weight_decay
        if self._apply_decay_fun is not None and not self._apply_decay_fun(p.name):
            wd = 0.0
        if self._lr_ratio is not None:
            # layer-wise LR scaling (reference adamw.py lr_ratio — the
            # ViT/LLRD fine-tuning knob): per-parameter multiplier
            lr = lr * float(self._lr_ratio(p))
        st = self._param_state(p)
        new_p, st["moment1"], st["moment2"] = _adam_update(
            p._data, g, lr, st["moment1"], st["moment2"], self._beta1, self._beta2,
            self._eps, self._step_count, True, wd)
        p._inplace_update(new_p)

    def _fused_aux(self, params):
        """Per-param hooks flattened once per bucket build: uniform values
        stay static scalars; varying ones become per-element f32 vectors."""
        from .fused import per_element_vector
        static, arrays = {}, {}
        wds = [0.0 if (self._apply_decay_fun is not None
                       and not self._apply_decay_fun(p.name))
               else self._weight_decay for p in params]
        if len(set(wds)) > 1:
            arrays["wd"] = per_element_vector(params, wds)
        else:
            static["wd"] = wds[0]
        if self._lr_ratio is not None:
            ratios = [float(self._lr_ratio(p)) for p in params]
            if len(set(ratios)) > 1:
                arrays["lr_ratio"] = per_element_vector(params, ratios)
            else:
                static["lr_ratio"] = ratios[0]
        return static, arrays


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _state_schema(self, p):
        return [("moment", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("inf_norm", lambda d: jnp.zeros(d.shape, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * p._data.astype(jnp.float32)
        m = self._beta1 * st["moment"] + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * st["inf_norm"], jnp.abs(g))
        st["moment"], st["inf_norm"] = m, u
        lr_t = lr / (1 - self._beta1 ** self._step_count)
        p._inplace_update((p._data.astype(jnp.float32) - lr_t * m / (u + self._eps)).astype(p._data.dtype))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None, weight_decay=None,
                 grad_clip=None, initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _state_schema(self, p):
        return [("moment", lambda d: jnp.full(d.shape, self._init_acc, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * p._data.astype(jnp.float32)
        st["moment"] = st["moment"] + jnp.square(g)
        p._inplace_update((p._data.astype(jnp.float32) -
                           lr * g / (jnp.sqrt(st["moment"]) + self._eps)).astype(p._data.dtype))


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._eps, self._rho = epsilon, rho

    def _state_schema(self, p):
        return [("avg_squared_grad", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("avg_squared_update", lambda d: jnp.zeros(d.shape, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * p._data.astype(jnp.float32)
        e_g = self._rho * st["avg_squared_grad"] + (1 - self._rho) * jnp.square(g)
        upd = jnp.sqrt(st["avg_squared_update"] + self._eps) / jnp.sqrt(e_g + self._eps) * g
        e_u = self._rho * st["avg_squared_update"] + (1 - self._rho) * jnp.square(upd)
        st["avg_squared_grad"], st["avg_squared_update"] = e_g, e_u
        p._inplace_update((p._data.astype(jnp.float32) - lr * upd).astype(p._data.dtype))


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._rho, self._eps, self._momentum, self._centered = rho, epsilon, momentum, centered

    def _state_schema(self, p):
        return [("mean_square", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("mean_grad", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("velocity", lambda d: jnp.zeros(d.shape, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * p._data.astype(jnp.float32)
        ms = self._rho * st["mean_square"] + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._rho * st["mean_grad"] + (1 - self._rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._eps)
            st["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._eps)
        v = self._momentum * st["velocity"] + lr * g / denom
        st["mean_square"], st["velocity"] = ms, v
        p._inplace_update((p._data.astype(jnp.float32) - v).astype(p._data.dtype))


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _state_schema(self, p):
        return [("moment1", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("moment2", lambda d: jnp.zeros(d.shape, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        g = g.astype(jnp.float32)
        pf = p._data.astype(jnp.float32)
        m = self._beta1 * st["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * st["moment2"] + (1 - self._beta2) * jnp.square(g)
        st["moment1"], st["moment2"] = m, v
        mhat = m / (1 - self._beta1 ** self._step_count)
        vhat = v / (1 - self._beta2 ** self._step_count)
        r = mhat / (jnp.sqrt(vhat) + self._eps)
        wd = 0.0 if (self._exclude_fn and self._exclude_fn(p)) else self._weight_decay
        r = r + wd * pf
        w_norm = jnp.linalg.norm(pf)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        p._inplace_update((pf - lr * trust * r).astype(p._data.dtype))


class NAdam(Optimizer):
    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 momentum_decay=0.004, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._psi = momentum_decay

    def _state_schema(self, p):
        return [("moment1", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("moment2", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("mu_prod", lambda d: jnp.ones([], jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        t = self._step_count
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * p._data.astype(jnp.float32)
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_t1 = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        mu_prod = st["mu_prod"] * mu_t
        st["mu_prod"] = mu_prod
        m = self._beta1 * st["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * st["moment2"] + (1 - self._beta2) * jnp.square(g)
        st["moment1"], st["moment2"] = m, v
        mhat = mu_t1 * m / (1 - mu_prod * mu_t1) + (1 - mu_t) * g / (1 - mu_prod)
        vhat = v / (1 - self._beta2 ** t)
        p._inplace_update((p._data.astype(jnp.float32) -
                           lr * mhat / (jnp.sqrt(vhat) + self._eps)).astype(p._data.dtype))


class RAdam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _state_schema(self, p):
        return [("moment1", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("moment2", lambda d: jnp.zeros(d.shape, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        t = self._step_count
        g = g.astype(jnp.float32)
        if self._weight_decay:
            g = g + self._weight_decay * p._data.astype(jnp.float32)
        m = self._beta1 * st["moment1"] + (1 - self._beta1) * g
        v = self._beta2 * st["moment2"] + (1 - self._beta2) * jnp.square(g)
        st["moment1"], st["moment2"] = m, v
        mhat = m / (1 - self._beta1 ** t)
        rho_inf = 2 / (1 - self._beta2) - 1
        # rho_t may be traced under jit.TrainStep: select, don't branch
        rho_t = rho_inf - 2 * t * self._beta2 ** t / (1 - self._beta2 ** t)
        vhat = jnp.sqrt(v / (1 - self._beta2 ** t))
        r2 = ((rho_t - 4) * (rho_t - 2) * rho_inf) / (
            (rho_inf - 4) * (rho_inf - 2) * jnp.maximum(rho_t, self._eps))
        r = jnp.sqrt(jnp.maximum(r2, 0.0))
        rect = r * mhat / (vhat + self._eps)
        upd = jnp.where(rho_t > 5, rect, mhat)
        p._inplace_update((p._data.astype(jnp.float32) - lr * upd).astype(p._data.dtype))


class ASGD(Optimizer):
    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _apply_one(self, p, g, lr):
        p._inplace_update(_sgd_update(p._data, g, lr, self._weight_decay))

    _fused_flat_update = SGD._fused_flat_update  # identical update math


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name)
        self._lr_range = learning_rate_range
        self._etas = etas

    def _state_schema(self, p):
        return [("prev_grad", lambda d: jnp.zeros(d.shape, jnp.float32)),
                ("step_size", lambda d: jnp.full(d.shape, self.get_lr()
                                                 if not isinstance(self.get_lr(), jax.Array)
                                                 else 0.001, jnp.float32))]

    def _apply_one(self, p, g, lr):
        st = self._param_state(p)
        g = g.astype(jnp.float32)
        sign = jnp.sign(g * st["prev_grad"])
        factor = jnp.where(sign > 0, self._etas[1], jnp.where(sign < 0, self._etas[0], 1.0))
        step = jnp.clip(st["step_size"] * factor, self._lr_range[0], self._lr_range[1])
        g_eff = jnp.where(sign < 0, 0.0, g)
        st["prev_grad"], st["step_size"] = g_eff, step
        p._inplace_update((p._data.astype(jnp.float32) - step * jnp.sign(g_eff)).astype(p._data.dtype))
