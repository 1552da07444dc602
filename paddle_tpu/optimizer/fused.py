"""Multi-tensor fused optimizer engine.

Analog of the reference's fused multi-tensor path
(python/paddle/optimizer/fusion_utils.py + the fused AdamW CUDA kernels in
PHI): instead of one jitted dispatch per parameter, parameters are grouped
into (param dtype, grad dtype, device) BUCKETS and each optimizer's update
math runs as ONE jitted, state-donated update over the bucket's flat
concatenated buffers. The new per-parameter views are unflattened inside
the same compiled program, so the eager ``Tensor`` API is unchanged and an
eager ``step()`` issues O(#buckets) compiled dispatches instead of
O(#params).

``ClipGradByGlobalNorm`` fuses into the same pass: one jitted concatenated
squared-norm reduction over every grad, with the scalar scale applied to
the flat grads inside each bucket update (one extra dispatch total, not one
per parameter). Optimizer state (moments/velocity) lives as persistent flat
buffers per bucket; ``sync_to_param_state`` materializes per-param views for
``state_dict`` / checkpointing, and bucket rebuilds re-seed from them.

A bucket is laid out once, when it is built: its length is the leaves' total
rounded up to ``kernels.fused_adamw.BUCKET_ALIGN`` (a multiple of every chunk
the Pallas kernel may run over) and everything that lives in or passes
through the bucket has that length — the state spans at rest, the
concatenated params and grads (a zero tail is the concat's last operand), the
per-element aux vectors and masks. So the padding lives here, in the layout,
not in the step: the kernel's own pad and slice find nothing to do, and no
bucket-sized copy stands between the state and the kernel. Leaves are cut
out by offset; nothing ever reads the tail.

This is the EAGER path, and only that. The buckets exist so that an eager
``step()`` costs O(#buckets) dispatches; inside a compiled step
(``jit.TrainStep``) there is one dispatch whatever the optimizer does, and on
a TPU a leaf in its tiled layout and its span of a 1-D buffer are different
layouts, so every ravel, concatenation operand and cut back into a leaf is a
pass over HBM (and under a mesh a gather of every shard). So
``Optimizer._apply`` never enters the engine when the gradients are tracers:
the compiled step updates each leaf where it lies, with per-param state, and
``Optimizer._flat_state_to_params`` carries the moments across when a model
moves between the two (one format at rest: ``state_dict`` is per parameter).

Fallbacks keep the per-param loop authoritative where flattening is wrong:
multi-device (sharded/replicated) params or states — distributed/sharding.py
owns those placements — and optimizers without ``_fused_flat_update``.
``FLAGS_fused_optimizer=False`` opts the eager path out globally.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..kernels.fused_adamw import BUCKET_ALIGN
from .clip import ClipGradByGlobalNorm, ClipGradByValue

# -- dispatch-count trace hook ---------------------------------------------
# Every compiled optimizer-update invocation (per-param `_apply_one` calls,
# fused bucket updates, the fused global-norm reduction) records itself
# here. The CI gate (tests/test_optimizer_dispatch_gate.py) reads the
# delta across one eager step() — the headline metric of
# the fused path is this count dropping from O(n_params) to O(n_buckets).

_DISPATCH = {"count": 0}


def record_dispatch(n: int = 1):
    _DISPATCH["count"] += n


def dispatch_count() -> int:
    return _DISPATCH["count"]


# -- helpers ----------------------------------------------------------------

def _is_traced(arrays) -> bool:
    return any(isinstance(a, jax.core.Tracer) for a in arrays)


def _multi_device(a) -> bool:
    try:
        return len(a.devices()) > 1
    except Exception:
        return False


def _device_key(a) -> str:
    try:
        devs = a.devices()
        if len(devs) == 1:
            return str(next(iter(devs)))
    except Exception:
        pass
    return "default"


def bucket_length(total: int) -> int:
    """A flat bucket's length: the leaves' ``total`` rounded up to the
    kernel's largest chunk, fixed when the bucket is built."""
    return -(-total // BUCKET_ALIGN) * BUCKET_ALIGN


def _concat_flat(arrays, length):
    """The leaves raveled into one flat span of ``length`` elements: a zero
    tail is the concatenation's last operand, so the span arrives at the
    bucket's length without a pass of its own."""
    parts = [a.ravel() for a in arrays]
    tail = length - sum(p.shape[0] for p in parts)
    if tail:
        parts.append(jnp.zeros(tail, parts[0].dtype))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def per_element_vector(params, values, dtype=jnp.float32):
    """Per-ELEMENT vector over a bucket's flat span from per-PARAM values
    (the lr_ratio / apply_decay_param_fun hooks become one broadcast),
    bucket-long: zero over the tail."""
    return _concat_flat(
        [jnp.full(p._data.size, float(v), dtype)
         for p, v in zip(params, values)],
        bucket_length(sum(p._data.size for p in params)))


class _Bucket:
    __slots__ = ("params", "idxs", "sizes", "shapes", "grad_dtype", "total",
                 "length", "state", "static", "aux", "fns", "masks")


class FusedOptimizerEngine:
    """Dtype/device-bucketed flat optimizer updates for one Optimizer.

    Owned lazily by an EAGER ``Optimizer.step`` and never entered under a
    trace: ``Optimizer._apply`` sends traced gradients (``jit.TrainStep``)
    to the per-param loop, and whoever wants the state per leaf takes it
    down first (``Optimizer._flat_state_to_params``).
    """

    def __init__(self, opt):
        self.opt = opt
        self.buckets: list[_Bucket] = []
        self._sig = None
        self._sig_set = frozenset()
        self._clip_fn = None
        self._clip_id = None
        self.last_dispatch_count = 0
        # True whenever the flat buffers are ahead of any per-param views
        # materialized into opt._state (sync_to_param_state clears it)
        self.state_dirty = False

    @property
    def active(self) -> bool:
        return bool(self.buckets)

    # -- bucket construction -------------------------------------------

    @staticmethod
    def _signature(params, grad_dtypes):
        return tuple(
            (id(p), tuple(p._data.shape), str(jnp.result_type(p._data)), gd)
            for p, gd in zip(params, grad_dtypes))

    def prime(self, params) -> bool:
        """Build buckets ahead of the first step (tenancy/tune.py: a step
        whose grads land on a subset then masks, never rebuilds): every
        param is assumed to participate with grad dtype == param dtype."""
        return self._build(
            params, [str(jnp.result_type(p._data)) for p in params])

    def invalidate(self):
        self._sig = None
        self._sig_set = frozenset()
        self.buckets = []

    def _build(self, params, grad_dtypes) -> bool:
        sig = self._signature(params, grad_dtypes)
        if sig == self._sig:
            return True
        # multi-device (sharded/replicated) params or states keep the
        # per-param path: flattening would collapse placements that
        # distributed/sharding.py deliberately installed (ZeRO stages)
        for p in params:
            if getattr(p, "_dist_attr", None) is not None \
                    or _multi_device(p._data):
                return False
            st = self.opt._state.get(id(p))
            if st and any(_multi_device(v) for v in st.values()):
                return False
        if self.buckets:
            # live flat state survives the rebuild via the per-param view
            self.sync_to_param_state()
        groups: dict = {}
        for i, (p, gd) in enumerate(zip(params, grad_dtypes)):
            key = (str(jnp.result_type(p._data)), gd, _device_key(p._data))
            groups.setdefault(key, []).append(i)
        self.buckets = [self._build_bucket(params, grad_dtypes, idxs)
                        for idxs in groups.values()]
        self._sig = sig
        self._sig_set = frozenset(sig)
        return True

    def _build_bucket(self, params, grad_dtypes, idxs) -> _Bucket:
        opt = self.opt
        b = _Bucket()
        b.idxs = list(idxs)
        b.params = [params[i] for i in idxs]
        b.shapes = [tuple(p._data.shape) for p in b.params]
        b.sizes = [int(np.prod(s)) for s in b.shapes]
        b.total = sum(b.sizes)
        # the layout, fixed here: every span of the bucket (state at rest,
        # params and grads in the step, aux vectors, masks) is this long
        b.length = bucket_length(b.total)
        b.grad_dtype = grad_dtypes[idxs[0]]
        b.static, b.aux = opt._fused_aux(b.params)
        b.fns = {}
        b.masks = {}
        # flat state: seed from any existing per-param state (checkpoint
        # loads, prior rebuilds), else the schema init — then drop the
        # per-param copies so state isn't held twice
        b.state = {}
        for name, init in opt._state_schema(b.params[0]):
            dt = jnp.result_type(init(b.params[0]._data))
            parts = []
            for p in b.params:
                v = (opt._state.get(id(p)) or {}).get(name)
                parts.append(jnp.ravel(v).astype(dt) if v is not None
                             else jnp.ravel(init(p._data)).astype(dt))
            b.state[name] = _concat_flat(parts, b.length)
        for p in b.params:
            opt._state.pop(id(p), None)
        self.state_dirty = True
        return b

    # -- state bridging (state_dict, hand-over to per-param state) ------

    def sync_to_param_state(self):
        """Materialize the flat buffers back into per-param ``opt._state``
        entries (state_dict, checkpointing, per-param fallback handoff)."""
        opt = self.opt
        self.state_dirty = False
        for b in self.buckets:
            for name, flat in b.state.items():
                off = 0
                for p, sz, shp in zip(b.params, b.sizes, b.shapes):
                    st = opt._state.setdefault(id(p), {})
                    st[name] = jax.lax.slice_in_dim(
                        flat, off, off + sz).reshape(shp)
                    off += sz

    # -- the fused step -------------------------------------------------

    def step(self, params, grads, lr) -> bool:
        """Apply one fused update. False → caller must run the per-param
        loop (unbuildable buckets: sharded params or state)."""
        grad_dtypes = [str(jnp.result_type(g)) for g in grads]
        sig = self._signature(params, grad_dtypes)
        if sig != self._sig:
            if self.active and self._sig_set.issuperset(sig):
                # a SUBSET of the primed params participates (MoE experts
                # off-route, freshly frozen params): mask their spans
                # instead of rebuilding — cheaper than a rebuild when
                # participation flickers
                return self._run(params, grads, lr, masked=True)
            if not self._build(params, grad_dtypes):
                return False
        return self._run(params, grads, lr, masked=False)

    def _run(self, params, grads, lr, masked: bool) -> bool:
        opt = self.opt
        clip = opt._grad_clip
        n = 0
        scale = None
        use_scale = isinstance(clip, ClipGradByGlobalNorm)
        if use_scale:
            scale = self._global_scale(grads)
            n += 1
        elif clip is not None and not isinstance(clip, ClipGradByValue):
            # per-tensor clips (ClipGradByNorm) stay eager; the flat update
            # still collapses the dispatches that dominate
            grads = clip._clip_arrays(params, grads)
        id2g = {id(p): g for p, g in zip(params, grads)}
        t = opt._step_count
        donate = jax.default_backend() != "cpu"
        for b in self.buckets:
            present = tuple(id(p) in id2g for p in b.params)
            if masked and not all(present):
                if not any(present):
                    continue  # whole bucket untouched this step
                g_arr = tuple(
                    id2g[id(p)] if ok else jnp.zeros(p._data.shape,
                                                     b.grad_dtype)
                    for p, ok in zip(b.params, present))
                mask = self._bucket_mask(b, present)
                fn = self._bucket_fn(b, use_scale, donate, use_mask=True)
            else:
                g_arr = tuple(id2g[id(p)] for p in b.params)
                mask = 1.0
                fn = self._bucket_fn(b, use_scale, donate, use_mask=False)
            p_arr = tuple(p._data for p in b.params)
            new_p, b.state = fn(p_arr, g_arr, b.state, b.aux, lr, t,
                                scale if scale is not None else 1.0, mask)
            record_dispatch()
            n += 1
            for p, a in zip(b.params, new_p):
                p._inplace_update(a)
        self.last_dispatch_count = n
        self.state_dirty = True  # per-param views in opt._state are stale
        return True

    _MASK_CACHE_MAX = 64

    def _bucket_mask(self, b, present):
        mask = b.masks.get(present)
        if mask is None:
            mask = jnp.asarray(np.concatenate(
                [np.full(sz, ok, bool)
                 for sz, ok in zip(b.sizes, present)]
                + [np.zeros(b.length - b.total, bool)]))
            # bound the cache: flickering participation (MoE routing) can
            # produce combinatorially many patterns, each mask is a full
            # bucket-sized array — evict oldest-inserted beyond the cap
            if len(b.masks) >= self._MASK_CACHE_MAX:
                b.masks.pop(next(iter(b.masks)))
            b.masks[present] = mask
        return mask

    def _global_scale(self, grads):
        """ClipGradByGlobalNorm as ONE jitted reduction over every grad."""
        clip = self.opt._grad_clip
        if self._clip_fn is None or self._clip_id != id(clip):
            self._clip_fn = jax.jit(lambda gs: clip._scale(list(gs)))
            self._clip_id = id(clip)
        record_dispatch()
        return self._clip_fn(tuple(grads))

    def _bucket_fn(self, b, use_scale, donate, use_mask):
        key = (use_scale, donate, use_mask)
        fn = b.fns.get(key)
        if fn is not None:
            return fn
        opt = self.opt
        # masked variants re-read flat_p and the old state AFTER the update
        # (the jnp.where pass-through); the Pallas kernel aliases those
        # buffers to its outputs in-place, so masked steps must keep the
        # jnp body (use-after-donation otherwise)
        upd = opt._fused_flat_update(b, allow_kernel=not use_mask)
        clip = opt._grad_clip
        byval = isinstance(clip, ClipGradByValue)
        vmin = clip.min if byval else 0.0
        vmax = clip.max if byval else 0.0
        l1 = opt._l1_decay
        sizes, shapes, length = list(b.sizes), list(b.shapes), b.length

        def body(p_arr, g_arr, state, aux, lr, t, scale, mask):
            # The tail past the leaves is zero in p, g and every state
            # span, and every rule the engine carries keeps it so: a zero
            # grad scaled, clipped round zero or L1'd stays zero; SGD and
            # Momentum give p - lr * (0 + wd * 0); Adam's moments stay 0
            # and its step is 0 / (0 + eps), the decoupled decay wd * 0.
            # Nothing reads it either way (the leaves are cut by offset).
            flat_p = _concat_flat(list(p_arr), length)
            flat_g = _concat_flat(list(g_arr), length)
            gdt = flat_g.dtype
            if use_scale:
                flat_g = (flat_g.astype(jnp.float32) * scale).astype(gdt)
            if byval:
                flat_g = jnp.clip(flat_g, vmin, vmax)
            if l1:
                # after clipping, like the per-param path
                flat_g = flat_g + l1 * jnp.sign(flat_p).astype(gdt)
            new_flat, new_state = upd(flat_p, flat_g, state, aux, lr, t)
            new_flat = new_flat.astype(flat_p.dtype)
            if use_mask:
                new_flat = jnp.where(mask, new_flat, flat_p)
                new_state = {k: jnp.where(mask, v, state[k])
                             for k, v in new_state.items()}
            outs, off = [], 0
            for sz, shp in zip(sizes, shapes):
                outs.append(jax.lax.slice_in_dim(
                    new_flat, off, off + sz).reshape(shp))
                off += sz
            return tuple(outs), new_state

        fn = jax.jit(body, donate_argnums=(2,) if donate else ())
        b.fns[key] = fn
        return fn
