"""GSPMD-native sharding: ONE partitioning layer for training and serving.

ROADMAP item 2. The distributed regimes that used to be separate
shard_map wrappers — data parallelism, tensor (Megatron) parallelism,
ZeRO optimizer-state sharding — collapse into *annotations* over ONE
logical 2-D device mesh::

    mesh axes:   ("data", "model") — plus "pipeline" under pp=K
    batch        -> P("data", ...)          activations shard on data
    stacked.*    -> P("pipeline", ...)      scan-stacked [L,...] leaves
                                            stage-slice on dim 0 (pp>1)
    q/k/v/gate/up-> P(..., "model")         column-parallel (out-dim)
    o/down       -> P(..., "model", None)   row-parallel (in-dim)
    embed        -> P("model", None)        vocab-sharded
    lm_head      -> P(None, "model")        vocab-sharded
    norms/biases -> P()                     replicated
    ZeRO         -> optimizer state         P("data") on a leaf's first
                                            whole, divisible dimension

The annotations ride the EXISTING single ``jax.jit`` executables —
``jit.TrainStep`` (training) and ``LLMEngine``'s ragged step (serving)
— as ``in_shardings``/``out_shardings``; XLA's GSPMD partitioner then
places every collective (the psum after a row-parallel matmul, the
grad all-reduce over data, the all-gather reassembling ZeRO-updated
params). Switching DP<->TP<->ZeRO changes ONLY the annotation preset:
no application code, no separate step function per regime — the
SNIPPETS exemplar's "8 chips to 6000-chip superclusters without
changing application code" contract.

Presets come from :class:`ShardingConfig` directly or from the
``FLAGS_gspmd`` string (``"dp=8"``, ``"tp=2,dp=4"``, ``"dp=8,zero"``,
…; empty = off). Everything here is provable chip-free: the tests
(tests/test_gspmd.py) run on an 8-device virtual CPU mesh
(``--xla_force_host_platform_device_count=8``) and read the collective
mix straight out of the compiled HLO.

What still needs a chip: the Pallas kernel tier (ragged attention,
fused dequant-matmul, decode megakernel) has no SPMD partitioning rule,
so under a mesh GSPMD falls back to gathering those operands — off-TPU
the jnp/interpret bodies partition fine (docs/DISTRIBUTED.md).
"""
from __future__ import annotations

import re

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.flags import GLOBAL_FLAGS, define_flag

DATA_AXIS = "data"
MODEL_AXIS = "model"
PIPELINE_AXIS = "pipeline"


class ShardingConfig:
    """One regime description: mesh degrees + the ZeRO toggle.

    ``data`` x ``model`` must equal (or -1-infer to) the device count.
    ``zero=True`` additionally shards the fused optimizer's flat state
    buckets over the data axis (ZeRO-1: per-device optimizer-state
    memory = global/data_degree; GSPMD all-gathers the updated params
    exactly where they are consumed). ``pipe=K`` adds the third mesh
    axis: the LayerStack's leading [L, ...] dim splits into K stages of
    L/K layers each, and TrainStep runs the 1F1B microbatch loop with
    collective-permute between stages (docs/DISTRIBUTED.md).
    """

    def __init__(self, data=-1, model=1, zero=False, pipe=1):
        self.data = int(data)
        self.model = int(model)
        self.zero = bool(zero)
        self.pipe = int(pipe)
        if self.model < 1:
            raise ValueError(f"model degree must be >= 1, got {model}")
        if self.pipe < 1:
            raise ValueError(f"pipeline degree must be >= 1, got {pipe}")
        if self.data < 1 and self.data != -1:
            raise ValueError(
                f"data degree must be >= 1 (or -1 to infer), got {data}")

    @classmethod
    def parse(cls, preset: str) -> "ShardingConfig | None":
        """``"dp=8"`` / ``"tp=2,dp=4"`` / ``"dp=8,zero"`` -> config;
        ``""`` -> None (GSPMD off). Raises ValueError on malformed
        presets — FLAGS_gspmd wires this through on_set, so an invalid
        ``flags.set`` rolls back instead of leaving a broken value."""
        preset = (preset or "").strip()
        if not preset:
            return None
        kw = {"data": -1, "model": 1, "zero": False, "pipe": 1}
        for part in preset.split(","):
            part = part.strip()
            if not part:
                continue
            if part == "zero":
                kw["zero"] = True
                continue
            m = re.fullmatch(
                r"(dp|tp|pp|data|model|pipe)\s*=\s*(-?\d+)", part)
            if not m:
                raise ValueError(
                    f"FLAGS_gspmd: cannot parse {part!r} (expected "
                    f"'dp=N', 'tp=N', 'pp=N', 'zero', comma-separated)")
            key = {"dp": "data", "tp": "model",
                   "pp": "pipe"}.get(m.group(1), m.group(1))
            kw[key] = int(m.group(2))
        return cls(**kw)

    def resolve(self, n_devices=None) -> "ShardingConfig":
        """Pin ``data=-1`` against the device count; validate the fit.

        With ``pipe > 1`` an explicit ``dp x tp x pp`` product that
        merely *divides* the device count is allowed — the mesh is built
        over the device prefix (`devices[:product]`), so `dp=2,pp=2`
        runs on the 8-device host mesh. ``pipe == 1`` keeps the exact
        2-D strictness (product must equal the device count)."""
        n = n_devices if n_devices is not None else len(jax.devices())
        data = self.data
        if data == -1:
            if n % (self.model * self.pipe):
                raise ValueError(
                    f"model x pipeline degree {self.model} x {self.pipe} "
                    f"does not divide the {n}-device mesh")
            data = n // (self.model * self.pipe)
        if self.pipe > 1:
            prod = data * self.model * self.pipe
            if prod > n or n % prod:
                raise ValueError(
                    f"mesh {data} x {self.model} x {self.pipe} "
                    f"(dp x tp x pp) does not divide {n} devices")
        elif data * self.model != n:
            raise ValueError(
                f"mesh {data} x {self.model} != {n} devices")
        out = ShardingConfig(data=data, model=self.model, zero=self.zero,
                             pipe=self.pipe)
        return out

    def __repr__(self):
        return (f"ShardingConfig(data={self.data}, model={self.model}, "
                f"zero={self.zero}, pipe={self.pipe})")

    def __eq__(self, other):
        return (isinstance(other, ShardingConfig)
                and (self.data, self.model, self.zero, self.pipe)
                == (other.data, other.model, other.zero, other.pipe))


def _check_gspmd(v):
    ShardingConfig.parse(str(v))   # raises -> flags.set rolls back


def _check_microbatches(v):
    if int(v) < 0:
        raise ValueError(
            f"FLAGS_pipeline_microbatches must be >= 0 (0 = auto), "
            f"got {v}")


define_flag("gspmd", str, "",
            "GSPMD sharding preset for jit.TrainStep: '' (off), 'dp=N', "
            "'tp=N[,dp=M]', 'pp=K', '...,zero' — DP/TP/PP/ZeRO as "
            "NamedSharding annotations over one (data, model, pipeline) "
            "mesh under the one compiled step (distributed/gspmd.py); "
            "collectives are placed by the XLA partitioner, no "
            "per-regime step code",
            on_set=_check_gspmd)

define_flag("pipeline_microbatches", int, 0,
            "Microbatch count M for the pp=K 1F1B pipeline loop inside "
            "jit.TrainStep; 0 = auto (M = pipeline degree K). The batch "
            "dim must divide by M; bubble fraction is (K-1)/(M+K-1), so "
            "larger M amortizes the fill/drain bubble "
            "(docs/DISTRIBUTED.md)",
            on_set=_check_microbatches)


def config_from_flags() -> ShardingConfig | None:
    return ShardingConfig.parse(GLOBAL_FLAGS.get("gspmd"))


def build_mesh(config: ShardingConfig, devices=None) -> Mesh:
    """The one logical ``(data, model[, pipeline])`` mesh.

    Built over ``jax.devices()`` in canonical order (real device ids —
    the multi-process regime's non-contiguous ids ride along exactly as
    in mesh.init_mesh). ``pipe > 1`` adds the third axis and may use a
    device prefix when dp x tp x pp divides (rather than equals) the
    device count; adjacent stages land on adjacent devices so the
    inter-stage collective-permute is a neighbor hop."""
    devs = list(devices) if devices is not None else jax.devices()
    cfg = config.resolve(len(devs))
    if cfg.pipe > 1:
        n = cfg.data * cfg.model * cfg.pipe
        arr = np.asarray(devs[:n]).reshape(cfg.data, cfg.model, cfg.pipe)
        return Mesh(arr, (DATA_AXIS, MODEL_AXIS, PIPELINE_AXIS))
    arr = np.asarray(devs).reshape(cfg.data, cfg.model)
    return Mesh(arr, (DATA_AXIS, MODEL_AXIS))


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
# Column-parallel projections shard their OUT dim (last axis of the
# [in, out] Linear layout), row-parallel their IN dim (second-to-last);
# counting from the END makes the same rule cover scan-stacked layouts
# ([n_layers, in, out]) untouched.
_COL_PAT = re.compile(
    r"(q_proj|k_proj|v_proj|gate_proj|up_proj)\.weight$")
_ROW_PAT = re.compile(r"(o_proj|down_proj)\.weight$")
_EMBED_PAT = re.compile(r"embed_tokens\.weight$")
_HEAD_PAT = re.compile(r"lm_head\.weight$")

#: extract_params layer-dict keys -> (which end-relative dim to shard)
_SERVING_COL = frozenset({"q", "k", "v", "gate", "up"})
_SERVING_ROW = frozenset({"o", "down"})


def _spec_from_end(ndim, end_axis, axis_name):
    """P with ``axis_name`` on dimension ``ndim - end_axis`` (1-based
    from the end), everything else None."""
    dims = [None] * ndim
    dims[ndim - end_axis] = axis_name
    return P(*dims)


def _divisible(shape, ndim, end_axis, degree) -> bool:
    if ndim < end_axis:
        return False
    return shape[ndim - end_axis] % degree == 0


def param_spec(name, shape, mesh) -> P:
    """NamedSharding rule for one NAMED parameter (training pytrees).

    Unknown names and non-divisible dims replicate — a model the rules
    don't recognize still runs, just without the TP split for that leaf.
    Under ``pp > 1`` the scan-stacked leaves (``stacked.*`` names, the
    LayerStack's [L, ...] layout) additionally shard dim 0 over the
    pipeline axis — the leading layer axis IS the stage dimension, so
    each stage holds its L/K layer slice; the TP rules compose on the
    end-relative dims of the same leaf.
    """
    ndim = len(shape)
    tp = mesh.shape.get(MODEL_AXIS, 1)
    pp = mesh.shape.get(PIPELINE_AXIS, 1)
    if ndim < 1:
        return P()
    dims = [None] * ndim
    if pp > 1 and ndim >= 2 and "stacked." in name \
            and shape[0] % pp == 0:
        dims[0] = PIPELINE_AXIS
    if tp > 1:
        end = None
        if _COL_PAT.search(name) and _divisible(shape, ndim, 1, tp):
            end = 1
        elif _ROW_PAT.search(name) and ndim >= 2 \
                and _divisible(shape, ndim, 2, tp):
            end = 2
        elif _EMBED_PAT.search(name) and ndim >= 2 \
                and _divisible(shape, ndim, 2, tp):
            end = 2   # vocab axis
        elif _HEAD_PAT.search(name) and _divisible(shape, ndim, 1, tp):
            end = 1   # vocab axis
        if end is not None and dims[ndim - end] is None:
            dims[ndim - end] = MODEL_AXIS
    if all(d is None for d in dims):
        return P()
    return P(*dims)


def named_param_shardings(named_shapes, mesh) -> dict:
    """{key: NamedSharding} for a {key: (name, shape)} map — the form
    jit.TrainStep's ``p{i}`` dict needs (keys are positional, names come
    from the model's named_parameters)."""
    return {k: NamedSharding(mesh, param_spec(name, shape, mesh))
            for k, (name, shape) in named_shapes.items()}


def _serving_leaf_spec(key, ndim, shape, tp):
    if tp <= 1:
        return P()
    if key in _SERVING_COL and ndim >= 1 and shape[-1] % tp == 0:
        return _spec_from_end(ndim, 1, MODEL_AXIS)
    if key in _SERVING_ROW and ndim >= 2 and shape[-2] % tp == 0:
        return _spec_from_end(ndim, 2, MODEL_AXIS)
    return P()


def _place_quantized(w, key, mesh, tp):
    """Shard a QuantizedWeight's payload+scale along the same logical
    dim as its fp counterpart. int8 payloads keep the [in, out] layout;
    int4 payloads are nibble-packed on the OUT dim, which still tiles
    evenly iff out/tp stays even — otherwise the leaf replicates."""
    from ..quantization.low_bit import QuantizedWeight
    q, s = w.qdata, w.scale
    if key in _SERVING_COL:
        ok = q.shape[-1] % tp == 0 and s.shape[-1] % tp == 0
        qs = _spec_from_end(q.ndim, 1, MODEL_AXIS) if ok else P()
        ss = _spec_from_end(s.ndim, 1, MODEL_AXIS) if ok else P()
    elif key in _SERVING_ROW:
        ok = q.ndim >= 2 and q.shape[-2] % tp == 0
        qs = _spec_from_end(q.ndim, 2, MODEL_AXIS) if ok else P()
        ss = P()
    else:
        qs = ss = P()
    return QuantizedWeight(
        jax.device_put(q, NamedSharding(mesh, qs)),
        jax.device_put(s, NamedSharding(mesh, ss)),
        w.bits, w.rows)


def shard_serving_params(params, mesh):
    """Place an ``extract_params`` pytree (fp or quantized) under the
    serving TP rules: projections split on the model axis, embed/lm_head
    on the vocab axis, norms replicated. Returns a new pytree of
    committed sharded arrays."""
    from ..quantization.low_bit import QuantizedWeight
    tp = mesh.shape.get(MODEL_AXIS, 1)

    def put(x, spec):
        return jax.device_put(x, NamedSharding(mesh, spec))

    out = {}
    e = params["embed"]
    out["embed"] = put(e, P(MODEL_AXIS, None)
                       if tp > 1 and e.shape[0] % tp == 0 else P())
    out["norm"] = put(params["norm"], P())
    if "lm_head" in params:
        lh = params["lm_head"]
        out["lm_head"] = put(lh, P(None, MODEL_AXIS)
                             if tp > 1 and lh.shape[-1] % tp == 0 else P())
    layers = []
    for lyr in params["layers"]:
        nl = {}
        for k, v in lyr.items():
            if isinstance(v, QuantizedWeight):
                nl[k] = _place_quantized(v, k, mesh, tp)
            else:
                nl[k] = put(v, _serving_leaf_spec(k, v.ndim, v.shape, tp))
        layers.append(nl)
    out["layers"] = layers
    return out


def kv_pool_sharding(mesh) -> NamedSharding:
    """Pool pages [Hkv, pages, ps, d] shard on the kv-head axis; the
    int8 scale rows [Hkv, pages] use :func:`kv_scale_sharding`."""
    return NamedSharding(mesh, P(MODEL_AXIS))


def kv_scale_sharding(mesh) -> NamedSharding:
    # fully-specified spec (not the P('model') prefix form): the ragged
    # step's OUTPUT scales come back as P('model', None), and a
    # spec-spelling mismatch between input and output re-keys the jit's
    # lowering cache — one spurious recompile per engine step
    return NamedSharding(mesh, P(MODEL_AXIS, None))


# ---------------------------------------------------------------------------
# training-state rules (jit.TrainStep)
# ---------------------------------------------------------------------------

def opt_state_shardings(opt_arrays, param_shardings_by_key, mesh,
                        zero=False) -> dict:
    """Shardings for TrainStep's optimizer-state dict: one array a leaf
    and state name, keyed ``{pkey}.{name}``.

    State mirrors its parameter's sharding when shapes line up (moments
    live where the param lives), else replicates. Under ``zero`` it is
    split over the data axis besides, on the first dimension that its
    parameter leaves whole and the data degree divides: ZeRO-1's
    state-memory split, a leaf at a time. The update is elementwise, so
    the partitioner runs each data rank's share of it where that share
    of the moments lies and gathers the updated parameter where it is
    consumed."""
    dp = mesh.shape.get(DATA_AXIS, 1)
    out = {}
    for k, v in opt_arrays.items():
        spec = P()
        ps = param_shardings_by_key.get(k.split(".", 1)[0])
        shape = local = tuple(getattr(v, "shape", ()))
        if ps is not None and shape:
            try:
                local, spec = ps.shard_shape(shape), ps.spec
            except Exception:       # shapes do not line up: replicate
                pass
        if zero and dp > 1:
            dims = list(spec) + [None] * (len(shape) - len(spec))
            for d, held in enumerate(dims):
                if held is None and local[d] % dp == 0:
                    dims[d] = DATA_AXIS
                    spec = P(*dims)
                    break
        out[k] = NamedSharding(mesh, spec)
    return out


def batch_sharding(arr, mesh) -> NamedSharding:
    """Batch tensors shard dim 0 over data (replicate when the batch
    does not divide — a ragged tail batch must not fail the step)."""
    dp = mesh.shape.get(DATA_AXIS, 1)
    if dp > 1 and getattr(arr, "ndim", 0) >= 1 and arr.shape[0] % dp == 0:
        return NamedSharding(mesh, P(DATA_AXIS))
    return NamedSharding(mesh, P())


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


#: (mesh, n_stages, n_microbatches) bound while TrainStep traces a
#: pp>1 program — LayerStack.forward switches to the pipelined scan
#: when this is set, without threading pipeline degrees through every
#: model signature
_PIPELINE_STACK: list = []


class pipeline_scope:
    def __init__(self, mesh, stages, microbatches):
        self.ctx = (mesh, int(stages), int(microbatches))

    def __enter__(self):
        _PIPELINE_STACK.append(self.ctx)
        return self.ctx

    def __exit__(self, *exc):
        _PIPELINE_STACK.pop()
        return False


def active_pipeline():
    """(mesh, n_stages, n_microbatches) or None."""
    return _PIPELINE_STACK[-1] if _PIPELINE_STACK else None


def stage_param_bytes(named_shapes_dtypes, pipe) -> tuple:
    """(max_stage_bytes, total_bytes) for {name: (shape, dtype)}.

    A ``stacked.*`` leaf whose dim 0 divides by ``pipe`` splits evenly
    across stages; everything else (embed, lm_head, norms) is counted on
    every stage (replicated) — the accounting behind the per-stage
    memory gate max_stage <= total/K + non-stacked slack."""
    per_stage = 0
    replicated_b = 0
    total = 0
    for name, (shape, dtype) in named_shapes_dtypes.items():
        nbytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
        total += nbytes
        if pipe > 1 and len(shape) >= 2 and "stacked." in name \
                and shape[0] % pipe == 0:
            per_stage += nbytes // pipe
        else:
            replicated_b += nbytes
    return per_stage + replicated_b, total


def predicted_pipeline_permutes(pipe) -> int:
    """Analytic count of pipeline-RING collective-permute instructions
    in the compiled pp-step HLO (see :func:`pipeline_permute_counts`).
    The scan body appears once in HLO regardless of tick count, so the
    count is structural, not ticks x (K-1): the forward shift-register
    roll (1) + the last-stage output collect (1) and their backward
    transposes plus the cotangent inject (3) = 5, independent of K, M,
    dp and tp (pinned by tests/test_pipeline_parallel.py across the
    preset matrix). Per-step *issue* count on the wire is
    ticks x (K-1) x 2 — that is latency accounting
    (docs/DISTRIBUTED.md), not an HLO instruction property."""
    return 5 if pipe > 1 else 0


# An op DEFINITION reads ``= <result type> opname(``; an operand reference
# reads ``%opname.3``. The result type is not parsed: a TPU layout such
# as ``f32[512]{0:T(8,128)S(1)}`` carries parentheses of its own, and a
# tuple type carries spaces. The op name standing free before ``(`` is
# what only a definition has.
_OP_DEF = r"(?<![%\w.-]){name}(?:-start)?\("

_CP_PAIRS_RE = re.compile(
    _OP_DEF.format(name="collective-permute")
    + r"[^\n]*?source_target_pairs=\{((?:\{\d+,\d+\},?)+)\}")


def pipeline_permute_counts(hlo_text: str, pipe: int) -> dict:
    """Split a compiled module's collective-permutes into pipeline RING
    hops vs partitioner resharding artifacts.

    The pipeline axis is always the INNERMOST mesh axis (build_mesh), so
    a stage hop moves a device index by exactly +-1 mod ``pipe`` within
    its block of ``pipe`` devices. An instruction counts as ``ring``
    when every source->target pair is such a neighbor hop — these are
    the structural inter-stage transfers the schedule demands (and what
    :func:`predicted_pipeline_permutes` predicts). Everything else
    (self-pairs, data/model-axis deltas) lands in ``other``: resharding
    the partitioner chose, which legitimately varies with shapes."""
    ring = other = 0
    for m in _CP_PAIRS_RE.finditer(hlo_text):
        pairs = re.findall(r"\{(\d+),(\d+)\}", m.group(1))

        def hop(a, b):
            a, b = int(a), int(b)
            return (a != b and a // pipe == b // pipe
                    and ((a % pipe + 1) % pipe == b % pipe
                         or (b % pipe + 1) % pipe == a % pipe))

        if pairs and all(hop(a, b) for a, b in pairs):
            ring += 1
        else:
            other += 1
    return {"ring": ring, "other": other, "total": ring + other}


# ---------------------------------------------------------------------------
# HLO forensics
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def collective_counts(hlo_text: str) -> dict:
    """Count collective ops in a compiled HLO module's text — the
    chip-free proof that an annotation preset produced the collective
    mix it promises (tests/test_gspmd.py, probe_gspmd). Start/done pairs
    of async collectives count once."""
    out = {}
    for name in _COLLECTIVES:
        # count op instances, not operand references (see _OP_DEF).
        # Async pairs define `-start`/`-done`; count the starts once.
        defs = re.findall(_OP_DEF.format(name=name), hlo_text)
        out[name.replace("-", "_")] = len(defs)
    return out


__all__ = [
    "DATA_AXIS", "MODEL_AXIS", "PIPELINE_AXIS", "ShardingConfig",
    "config_from_flags", "build_mesh", "param_spec",
    "named_param_shardings", "shard_serving_params", "kv_pool_sharding",
    "kv_scale_sharding", "opt_state_shardings", "batch_sharding",
    "replicated", "collective_counts", "pipeline_scope",
    "active_pipeline", "stage_param_bytes",
    "predicted_pipeline_permutes", "pipeline_permute_counts",
]
