"""Functional collectives — the ProcessGroup capability surface.

TPU-native analog of the reference's collective runtime (reference:
paddle/phi/core/distributed/collective/process_group.h:130-345 — AllGather,
AllReduce, AllToAll, Barrier, Broadcast, Reduce, ReduceScatter, Scatter,
Send/Recv; Python wrappers python/paddle/distributed/communication/). Two
execution regimes, matching how TPU programs are actually written:

1. **Inside a shard_map / pjit-manual region** (an axis name is bound):
   collectives lower to XLA collective HLOs over ICI — ``lax.psum``,
   ``all_gather``, ``ppermute``, ``all_to_all``. This is the analog of the
   reference's device-side NCCL kernels.
2. **Eager, multi-process** (after ``init_parallel_env`` under the launch
   CLI): each process holds its own local value; collectives really
   communicate across processes. Global-group reductions/gathers ride a
   jitted all-gather over the process-spanning device mesh
   (jax.experimental.multihost_utils); strict-subgroup collectives and p2p
   send/recv use the coordination-service key-value store (the TCPStore
   analog) as a mailbox, so — like the reference's ProcessGroup — only the
   group's member ranks need to enter the call. This is the regime the
   reference's ProcessGroup tests exercise
   (test/legacy_test/test_collective_api_base.py:192).
3. **Eager, single process**: world size 1 — the identity semantics of
   every collective are then exact, not a stub.

Groups are mesh-axis subsets (see fleet/topology.py) or explicit rank
lists; a ``Group``'s ``axis_name`` binds collectives inside shard_map
regions, its ``ranks`` select the subgroup in the multi-process regime.
"""
from __future__ import annotations

import base64
import pickle

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.flags import GLOBAL_FLAGS
from ..core.tensor import Tensor

# ---- reduce ops (process_group.h ReduceOp) ----


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = a named mesh axis (or explicit rank list).

    Reference: python/paddle/distributed/communication/group.py:29. On TPU
    the group's collectives ride the mesh axis; ``axis_name`` is what binds
    them inside shard_map regions.
    """

    def __init__(self, ranks, axis_name=None, pg_id=0):
        self.ranks = list(ranks)
        self.nranks = len(self.ranks)
        self.axis_name = axis_name
        self.id = pg_id

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis_name}, ranks={self.ranks})"


_default_group: Group | None = None


def _get_axis(group):
    if group is not None and group.axis_name is not None:
        return group.axis_name
    return None


def _in_manual_region(axis_name) -> bool:
    """True when ``axis_name`` is bound by an enclosing shard_map."""
    if axis_name is None:
        return False
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def _apply(x, fn):
    if isinstance(x, Tensor):
        out = fn(x._data)
        if GLOBAL_FLAGS.get("sync_nccl_allreduce") \
                and not isinstance(out, jax.core.Tracer):
            # blocking-collective mode (reference FLAGS_sync_nccl_allreduce):
            # surface comm failures at the call site, not at next readback
            jax.block_until_ready(out)
        x._data = out
        return x
    return fn(x)


def _mp_active() -> bool:
    """True in the eager multi-process regime (launch CLI + jax.distributed)."""
    try:
        return jax.process_count() > 1
    except RuntimeError:
        return False


def _group_ranks(group):
    if group is not None and group.ranks:
        return list(group.ranks)
    return list(range(get_world_size()))


def _group_index(group, rank, what="rank"):
    ranks = _group_ranks(group)
    if rank not in ranks:
        raise ValueError(f"{what} {rank} is not a member of group "
                         f"ranks={ranks}")
    return ranks.index(rank)


def _is_global(ranks) -> bool:
    return set(ranks) == set(range(get_world_size()))


def _nonmember_noop(group) -> bool:
    """Reference semantics (_warn_cur_rank_not_in_group,
    python/paddle/distributed/communication/group.py): a rank outside the
    group warns and no-ops the collective instead of raising."""
    ranks = _group_ranks(group)
    if get_rank() in ranks:
        return False
    import warnings
    warnings.warn(f"rank {get_rank()} is not in group ranks={ranks}; "
                  "the collective is a no-op on this rank")
    return True


_coll_seq: dict[tuple, int] = {}


def _group_tag(gkey) -> str:
    """KV prefix distinguishing groups by BOTH id and member ranks —
    groups that share pg_id (e.g. ad-hoc Group objects with the default
    id=0) must not collide on coordination-service keys."""
    import zlib
    return f"{gkey[0]}-{zlib.crc32(repr(gkey[1]).encode()) & 0xFFFFFFFF:x}"


def _subgroup_exchange(payload, group, ranks):
    """True subgroup all-gather over the coordination-service KV store:
    ONLY the group's members call (reference ProcessGroup semantics —
    process_group.h requires just the group's ranks to enter a collective,
    so an mp-subgroup all_reduce must not block on unrelated ranks).

    Each member publishes its pickled payload under a (group, seq, rank)
    key, then blocking-reads every peer's key. A member's key from two
    rounds back is deleted when it publishes round ``seq``: reaching round
    ``seq`` means every peer finished round ``seq-1``, which required their
    reads of round ``seq-2`` — so the store stays bounded at 2 rounds.
    Returns the payloads in group-rank order.
    """
    me = get_rank()
    if me not in ranks:
        raise ValueError(f"rank {me} called a collective on group "
                         f"ranks={ranks} it is not a member of")
    client = _kv_client()
    gkey = (group.id if group is not None else 0, tuple(ranks))
    seq = _coll_seq.get(gkey, 0)
    _coll_seq[gkey] = seq + 1
    prefix = f"ptpu_coll/{_group_tag(gkey)}"
    blob = base64.b64encode(pickle.dumps(payload)).decode()
    client.key_value_set(f"{prefix}/{seq}/{me}", blob)
    if seq >= 2:
        try:
            client.key_value_delete(f"{prefix}/{seq - 2}/{me}")
        except Exception:
            pass
    from .watchdog import maybe_track
    out = []
    for r in ranks:
        if r == me:
            out.append(payload)
            continue
        with maybe_track("subgroup_exchange",
                         meta={"rank": me, "peer": r, "seq": seq}):
            raw = client.blocking_key_value_get(f"{prefix}/{seq}/{r}",
                                                120_000)
        out.append(pickle.loads(base64.b64decode(raw)))
    return out


_bcast_src_hist: dict[tuple, dict[int, int]] = {}


def _subgroup_bcast(payload, group, ranks, src):
    """Direct subgroup broadcast over the KV store: src publishes once and
    each member reads only src's key — O(n) coordination-service RPCs
    instead of routing through the full O(n^2) exchange. Readers ack each
    round; before publishing round ``seq`` the current src blocking-reads
    every READER ack from round ``seq-2`` (using that round's recorded src
    — it may differ) and only then deletes that round's keys, so a slow
    reader can never find its key already garbage-collected."""
    me = get_rank()
    client = _kv_client()
    gkey = (group.id if group is not None else 0, tuple(ranks))
    skey = (gkey, "bcast")
    seq = _coll_seq.get(skey, 0)
    _coll_seq[skey] = seq + 1
    hist = _bcast_src_hist.setdefault(skey, {})
    hist[seq] = src
    prefix = f"ptpu_coll/{_group_tag(gkey)}/b"
    from .watchdog import maybe_track
    if me == src:
        if seq >= 2:
            old = seq - 2
            old_src = hist.pop(old, src)
            for r in ranks:
                # readers of round `old` wrote acks; its src did not.
                # `me` skips its own ack — reaching here means it finished.
                if r == old_src or r == me:
                    continue
                with maybe_track("subgroup_bcast_ack",
                                 meta={"rank": me, "peer": r, "seq": old}):
                    client.blocking_key_value_get(
                        f"{prefix}/{old}/ack{r}", 120_000)
                try:
                    client.key_value_delete(f"{prefix}/{old}/ack{r}")
                except Exception:
                    pass
            for k in (f"{prefix}/{old}/{old_src}", f"{prefix}/{old}/ack{me}"):
                try:
                    client.key_value_delete(k)
                except Exception:
                    pass
        blob = base64.b64encode(pickle.dumps(payload)).decode()
        client.key_value_set(f"{prefix}/{seq}/{src}", blob)
        return payload
    hist.pop(seq - 2, None)
    with maybe_track("subgroup_bcast",
                     meta={"rank": me, "src": src, "seq": seq}):
        raw = client.blocking_key_value_get(f"{prefix}/{seq}/{src}", 120_000)
    client.key_value_set(f"{prefix}/{seq}/ack{me}", "1")
    return pickle.loads(base64.b64decode(raw))


def _gather_rows(a, group):
    """Host all-gather of every group rank's local value, as rows.

    Global group: one jitted all-gather over the process-spanning mesh
    (fast path — rides ICI/DCN). Strict-subset group: the KV-mailbox
    subgroup exchange, so only members participate."""
    ranks = _group_ranks(group)
    arr = np.asarray(a)
    if not _is_global(ranks):
        return np.stack(_subgroup_exchange(arr, group, ranks))
    from jax.experimental import multihost_utils
    from .watchdog import maybe_track
    with maybe_track("process_allgather",
                     meta={"rank": get_rank(), "shape": np.shape(a)}):
        rows = multihost_utils.process_allgather(arr)
    return np.stack([rows[r] for r in ranks])


def _np_reduce(rows, op):
    if op == ReduceOp.SUM:
        return rows.sum(axis=0)
    if op == ReduceOp.MAX:
        return rows.max(axis=0)
    if op == ReduceOp.MIN:
        return rows.min(axis=0)
    if op == ReduceOp.AVG:
        return rows.mean(axis=0)
    if op == ReduceOp.PROD:
        return rows.prod(axis=0)
    raise ValueError(f"unknown reduce op {op!r}")


# ---- quantized gradient all-reduce (EQuARX, arxiv: Efficient Quantized
# AllReduce in XLA). DP grad sync is bandwidth-bound exactly like decode:
# the payload each rank moves per step is the full gradient footprint, so
# int8 chunks + one fp32 scale per chunk cut the bytes ~4x. Off by
# default (FLAGS_quantized_allreduce); the disabled path is bit-identical
# to the plain sync. ----


def _quant_chunk_elems() -> int:
    return max(int(GLOBAL_FLAGS.get("quantized_allreduce_chunk_elems")), 1)


def chunk_quantize(arr, chunk_elems=None):
    """Symmetric per-chunk int8 quantization of a host fp buffer.

    Returns ``(q [C, chunk] int8, scales [C] f32, n)`` — the payload +
    sideband a rank actually ships. One fp32 scale per ``chunk_elems``
    values bounds the relative error per element by ~1/254 of the chunk's
    amax (round-to-nearest over 127 steps). The chunk never exceeds the
    buffer: a small buffer ships small (no 64Ki zero-pad for a scalar).
    """
    chunk = chunk_elems or _quant_chunk_elems()
    a = np.asarray(arr, np.float32).ravel()
    n = a.size
    chunk = min(chunk, max(n, 1))
    pad = (-n) % chunk
    if pad:
        a = np.concatenate([a, np.zeros(pad, np.float32)])
    a2 = a.reshape(-1, chunk)
    scales = (np.maximum(np.abs(a2).max(axis=1), 1e-30) / 127.0) \
        .astype(np.float32)
    q = np.clip(np.rint(a2 / scales[:, None]), -127, 127).astype(np.int8)
    return q, scales, n


def chunk_dequantize(q, scales, n):
    return (q.astype(np.float32) * scales[:, None]).ravel()[:n]


#: error-feedback residuals keyed by caller-stable buffer name: the part
#: of the local gradient the int8 payload could not carry is re-injected
#: into the NEXT round's payload instead of being lost (EQuARX §error
#: feedback) — over steps the quantization bias cancels instead of
#: accumulating in the optimizer state. Each entry carries the REGIME
#: SIGNATURE it was produced under — (group axis name, member ranks,
#: buffer shape) — so switching parallel regimes or meshes mid-run
#: (e.g. re-wrapping a model onto a different dp subgroup, or a bucket
#: name colliding across two communicators) can never silently
#: re-inject a residual that belongs to a different reduction: the
#: mismatch warns and resets instead.
_EF_RESIDUALS: dict = {}


def _ef_regime_sig(group, arr):
    return (_get_axis(group), tuple(_group_ranks(group)),
            tuple(np.shape(arr)))


def reset_quantized_allreduce_residuals():
    _EF_RESIDUALS.clear()


def _quantized_sum_payloads(payloads, n):
    """Dequantize-and-sum every rank's (q, scales) payload — the reduce
    half each rank runs locally after the exchange (split out so the
    error-bound gate can drive it without processes)."""
    out = None
    for q, scales in payloads:
        d = q.astype(np.float32) * scales[:, None]
        out = d if out is None else out + d
    return out.ravel()[:n]


def quantized_all_reduce_sum(a, group=None, error_feedback_key=None):
    """Chunk-wise int8 SUM all-reduce of one host fp buffer.

    Each rank quantizes its LOCAL value (plus any carried residual) into
    int8 chunks, ships payload + per-chunk scales, and sums the
    dequantized contributions — one quantization error per rank per
    element, never compounded through the reduction tree. World size 1 is
    the identity (no quantization: nothing travels, so nothing is cut).
    """
    arr = np.asarray(a, np.float32)
    if not _mp_active():
        return arr
    if _nonmember_noop(group):   # same warn+no-op contract as all_reduce
        return arr
    ranks = _group_ranks(group)
    local = arr
    use_ef = error_feedback_key is not None and \
        GLOBAL_FLAGS.get("quantized_allreduce_error_feedback")
    sig = _ef_regime_sig(group, arr) if use_ef else None
    if use_ef:
        ent = _EF_RESIDUALS.get(error_feedback_key)
        if ent is not None:
            stored_sig, res = ent
            if stored_sig == sig:
                local = arr + res
            else:
                import warnings
                warnings.warn(
                    f"quantized all-reduce error feedback: residual for "
                    f"bucket {error_feedback_key!r} was recorded under "
                    f"regime {stored_sig} but this reduction runs under "
                    f"{sig} (mesh/group/shape changed mid-run) — "
                    f"resetting the residual instead of re-injecting a "
                    f"stale correction", stacklevel=2)
                _EF_RESIDUALS.pop(error_feedback_key, None)
    q, scales, n = chunk_quantize(local)
    if use_ef:
        _EF_RESIDUALS[error_feedback_key] = (
            sig,
            (local.ravel() - chunk_dequantize(q, scales, n))
            .reshape(arr.shape))
    if not _is_global(ranks):
        payloads = _subgroup_exchange((q, scales), group, ranks)
    else:
        from jax.experimental import multihost_utils
        from .watchdog import maybe_track
        with maybe_track("quantized_allreduce",
                         meta={"rank": get_rank(), "bytes": q.nbytes}):
            # ONE collective launch: payload + scale sideband travel as a
            # pytree through the same all-gather
            q_rows, s_rows = multihost_utils.process_allgather((q, scales))
        payloads = [(q_rows[r], s_rows[r]) for r in ranks]
    return _quantized_sum_payloads(payloads, n).reshape(arr.shape)


def _quantized_model_jnp(a):
    """In a shard_map/manual region the collective itself is an XLA HLO —
    int8 payload framing needs a compiler pass there (EQuARX is one), so
    this regime models the numerics: each rank's contribution is chunk-
    quantized BEFORE the psum, giving the same per-rank error contract as
    the eager int8 exchange (parity between regimes is what the tests
    pin)."""
    chunk = _quant_chunk_elems()
    flat = a.astype(jnp.float32).ravel()
    n = flat.size
    pad = (-n) % chunk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.float32)])
    a2 = flat.reshape(-1, chunk)
    scales = jnp.maximum(jnp.max(jnp.abs(a2), axis=1), 1e-30) / 127.0
    q = jnp.clip(jnp.round(a2 / scales[:, None]), -127, 127)
    deq = (q * scales[:, None]).ravel()[:n]
    return deq.reshape(a.shape).astype(a.dtype)


def _quantized_route(a, op) -> bool:
    """Does FLAGS_quantized_allreduce apply to this value/op?

    The flag is a global collective transform (the EQuARX shape: an
    in-XLA pass would see every all-reduce), but only BANDWIDTH-BOUND
    reductions profit: buffers below ``quantized_allreduce_min_elems``
    (loss scalars, metric reductions) stay exact — quantizing them buys
    nothing and costs eval fidelity.
    """
    if not GLOBAL_FLAGS.get("quantized_allreduce"):
        return False
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        return False
    if not np.issubdtype(np.dtype(getattr(a, "dtype", np.float32)),
                         np.floating):
        return False
    size = int(np.prod(getattr(a, "shape", ()) or (1,)))
    return size >= int(GLOBAL_FLAGS.get("quantized_allreduce_min_elems"))


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place all-reduce (reference: process_group.h AllReduce;
    python/paddle/distributed/communication/all_reduce.py).

    ``FLAGS_quantized_allreduce`` reroutes float SUM/AVG reductions
    through the chunk-wise int8 path (grad sync's bandwidth cut); the
    flag off, this body is untouched — bit-identical to the plain sync.
    """
    axis = _get_axis(group)

    def fn(a):
        if _in_manual_region(axis):
            if _quantized_route(a, op):
                aq = _quantized_model_jnp(a)
                return lax.psum(aq, axis) if op == ReduceOp.SUM \
                    else lax.pmean(aq, axis)
            if op == ReduceOp.SUM:
                return lax.psum(a, axis)
            if op == ReduceOp.MAX:
                return lax.pmax(a, axis)
            if op == ReduceOp.MIN:
                return lax.pmin(a, axis)
            if op == ReduceOp.AVG:
                return lax.pmean(a, axis)
            if op == ReduceOp.PROD:
                return jnp.exp(lax.psum(jnp.log(a), axis))
        if _mp_active():
            if _nonmember_noop(group):
                return a
            if _quantized_route(a, op):
                out = quantized_all_reduce_sum(np.asarray(a), group)
                if op == ReduceOp.AVG:
                    out = out / len(_group_ranks(group))
            else:
                out = _np_reduce(_gather_rows(a, group), op)
            return jnp.asarray(out.astype(
                getattr(a, "dtype", np.asarray(a).dtype), copy=False))
        return a  # world size 1: reduction of one value

    return _apply(tensor, fn)


def raw_all_reduce_sum(a, group=None):
    """Sum-reduce a RAW jnp array across the group, usable inside an op
    body (fused ops that must reduce a partial product mid-computation,
    e.g. fused_multi_head_attention's out-projection). Manual/shard_map
    regions lower to ``lax.psum`` (differentiable, rides ICI); the eager
    multi-process regime uses the host exchange; world size 1 is the
    identity."""
    axis = _get_axis(group)
    if _in_manual_region(axis):
        return lax.psum(a, axis)
    if _mp_active():
        if _nonmember_noop(group):
            return a
        if isinstance(a, jax.core.Tracer):
            raise NotImplementedError(
                "raw_all_reduce_sum: the eager multi-process host exchange "
                "cannot run under autograd/jit tracing — run tensor-parallel "
                "training through shard_map/GSPMD (group with a bound "
                "axis_name), or call the fused op with stop_gradient inputs")
        out = _np_reduce(_gather_rows(a, group), ReduceOp.SUM)
        return jnp.asarray(out.astype(a.dtype, copy=False))
    return a


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    """Gather shards from every rank (process_group.h AllGather)."""
    ax = _get_axis(group)
    if isinstance(tensor, Tensor) and _in_manual_region(ax):
        out = lax.all_gather(tensor._data, ax, axis=axis, tiled=False)
        n = out.shape[axis]
        parts = [Tensor(jnp.take(out, i, axis=axis)) for i in range(n)]
        tensor_list.extend(parts)
        return tensor_list
    if _mp_active():
        if _nonmember_noop(group):
            return tensor_list
        rows = _gather_rows(tensor._data if isinstance(tensor, Tensor)
                            else tensor, group)
        tensor_list.extend(Tensor(jnp.asarray(r)) for r in rows)
        return tensor_list
    tensor_list.append(Tensor(tensor._data))
    return tensor_list


def _allgather_bytes(payload: bytes, group=None) -> list[bytes]:
    """Gather arbitrary bytes from every rank (length-prefixed, padded)."""
    from jax.experimental import multihost_utils
    ranks = _group_ranks(group)
    if not _is_global(ranks):
        return _subgroup_exchange(payload, group, ranks)
    n = len(payload)
    lens = multihost_utils.process_allgather(np.asarray([n], np.int32))
    cap = int(lens.max())
    buf = np.zeros(cap, np.uint8)
    buf[:n] = np.frombuffer(payload, np.uint8)
    rows = multihost_utils.process_allgather(buf)
    out = []
    for r in _group_ranks(group):
        out.append(bytes(rows[r][:int(lens.reshape(-1)[r])]))
    return out


def all_gather_object(obj_list, obj, group=None):
    if _mp_active():
        if _nonmember_noop(group):
            return obj_list
        for blob in _allgather_bytes(pickle.dumps(obj), group):
            obj_list.append(pickle.loads(blob))
        return obj_list
    obj_list.append(obj)
    return obj_list


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """(process_group.h ReduceScatter)."""
    ax = _get_axis(group)
    ins = tensor_or_tensor_list
    if _in_manual_region(ax):
        a = ins._data if isinstance(ins, Tensor) else jnp.concatenate(
            [t._data for t in ins], axis=0)
        out = lax.psum_scatter(a, ax, scatter_dimension=0, tiled=True)
        tensor._data = out
        return tensor
    if _mp_active():
        if _nonmember_noop(group):
            return tensor
        a = ins._data if isinstance(ins, Tensor) else jnp.concatenate(
            [t._data for t in ins], axis=0)
        rows = _gather_rows(a, group)
        red = _np_reduce(rows, op)
        ranks = _group_ranks(group)
        me = _group_index(group, get_rank())
        chunk = red.shape[0] // len(ranks)
        tensor._data = jnp.asarray(red[me * chunk:(me + 1) * chunk])
        return tensor
    tensor._data = (ins[0]._data if isinstance(ins, (list, tuple))
                    else ins._data)
    return tensor


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """(process_group.h AllToAll) — inside shard_map uses lax.all_to_all."""
    ax = _get_axis(group)
    if _in_manual_region(ax):
        stacked = jnp.stack([t._data for t in in_tensor_list], axis=0)
        out = lax.all_to_all(stacked, ax, split_axis=0, concat_axis=0,
                             tiled=False)
        for i in range(out.shape[0]):
            out_tensor_list.append(Tensor(out[i]))
        return out_tensor_list
    if _mp_active():
        if _nonmember_noop(group):
            return out_tensor_list
        stacked = np.stack([np.asarray(t._data) for t in in_tensor_list])
        rows = _gather_rows(stacked, group)       # [n, n, ...]
        ranks = _group_ranks(group)
        me = _group_index(group, get_rank())
        out_tensor_list.extend(Tensor(jnp.asarray(rows[j][me]))
                               for j in range(len(ranks)))
        return out_tensor_list
    out_tensor_list.extend(Tensor(t._data) for t in in_tensor_list)
    return out_tensor_list


def broadcast(tensor, src=0, group=None, sync_op=True):
    """(process_group.h Broadcast)."""
    if _mp_active():
        if _nonmember_noop(group):
            return tensor
        _group_index(group, src, what="src")
        ranks = _group_ranks(group)
        if not _is_global(ranks):
            # only src's bytes travel — readers must not pay a host
            # materialization of their own (discarded) value
            a = np.asarray(tensor._data if isinstance(tensor, Tensor)
                           else tensor) if get_rank() == src else None
            val = jnp.asarray(_subgroup_bcast(a, group, ranks, src))
        else:
            a = np.asarray(tensor._data if isinstance(tensor, Tensor)
                           else tensor)
            from jax.experimental import multihost_utils
            val = jnp.asarray(multihost_utils.broadcast_one_to_all(
                a, is_source=get_rank() == src))
        if isinstance(tensor, Tensor):
            tensor._data = val
            return tensor
        return Tensor(val)
    return tensor  # single process: already consistent


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """(process_group.h Reduce) — every rank computes; only dst's value is
    contractually meaningful, matching the reference's observable behavior."""
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    if _mp_active():
        if _nonmember_noop(group):
            return tensor
        # src's list is authoritative: broadcast it, pick own chunk
        # only src's list travels: non-src ranks contribute a tiny None blob
        payload = pickle.dumps([np.asarray(t._data) for t in tensor_list]
                               if tensor_list else None)
        blobs = _allgather_bytes(payload, group)
        src_idx = _group_index(group, src, what="src")
        src_list = pickle.loads(blobs[src_idx])
        if src_list is None:
            raise ValueError(f"scatter: src rank {src} passed no tensor_list")
        me = _group_index(group, get_rank())
        tensor._data = jnp.asarray(src_list[me])
        return tensor
    if tensor_list:
        rank = get_rank()
        idx = group.get_group_rank(rank) if group is not None else rank
        tensor._data = tensor_list[max(idx, 0)]._data
    return tensor


# ---- p2p over the coordination-service KV store (TCPStore analog) ----

_p2p_seq: dict[tuple, int] = {}


def _kv_client():
    from jax._src import distributed as _dist
    client = getattr(_dist.global_state, "client", None)
    if client is None:
        raise RuntimeError(
            "p2p send/recv needs the multi-process regime "
            "(init_parallel_env under the launch CLI)")
    return client


def send(tensor, dst=0, group=None, sync_op=True):
    """P2P send (process_group.h Send). Inside shard_map: ppermute edge;
    eager multi-process: mailbox on the coordination service."""
    ax = _get_axis(group)
    if _in_manual_region(ax):
        n = lax.axis_size(ax)
        tensor._data = lax.ppermute(tensor._data, ax,
                                    [(i, dst) for i in range(n)])
        return tensor
    if _mp_active():
        me = get_rank()
        seq = _p2p_seq.get((me, dst), 0)
        _p2p_seq[(me, dst)] = seq + 1
        arr = np.asarray(tensor._data if isinstance(tensor, Tensor)
                         else tensor)
        blob = base64.b64encode(pickle.dumps(arr)).decode()
        _kv_client().key_value_set(f"ptpu_p2p/{me}->{dst}/{seq}", blob)
        return tensor
    raise RuntimeError("send() has no peer in a single-process program; use "
                       "it under the launch CLI or inside shard_map")


def recv(tensor, src=0, group=None, sync_op=True):
    if _in_manual_region(_get_axis(group)):
        return tensor  # pair of the ppermute in send()
    if _mp_active():
        me = get_rank()
        seq = _p2p_seq.get((src, me), 0)
        _p2p_seq[(src, me)] = seq + 1
        key = f"ptpu_p2p/{src}->{me}/{seq}"
        client = _kv_client()
        from .watchdog import maybe_track
        with maybe_track("recv", meta={"src": src, "dst": me, "seq": seq}):
            blob = client.blocking_key_value_get(key, 120_000)
        try:  # consumed: keep the coordination service's store bounded
            client.key_value_delete(key)
        except Exception:
            pass
        arr = pickle.loads(base64.b64decode(blob))
        tensor._data = jnp.asarray(arr)
        return tensor
    raise RuntimeError("recv() has no peer in a single-process program; use "
                       "it under the launch CLI or inside shard_map")


def barrier(group=None):
    if _mp_active():
        if _nonmember_noop(group):
            return
        ranks = _group_ranks(group)
        if not _is_global(ranks):
            _subgroup_exchange(b"", group, ranks)
            return
        from jax.experimental import multihost_utils
        from .watchdog import maybe_track
        with maybe_track("barrier", meta={"rank": get_rank()}):
            multihost_utils.sync_global_devices("paddle_tpu_barrier")
        return
    jax.block_until_ready(jnp.zeros(()))


def stream_all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
                      use_calc_stream=False):
    """paddle.distributed.communication.stream.* variants collapse to the
    same XLA collectives (streams are XLA's async domain on TPU)."""
    return all_reduce(tensor, op, group, sync_op)


# ---- environment (python/paddle/distributed/parallel.py ParallelEnv) ----


def get_rank(group=None):
    try:
        rank = jax.process_index()
    except RuntimeError:
        rank = 0
    if group is not None:
        return group.get_group_rank(rank)
    return rank


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    try:
        return jax.process_count()
    except RuntimeError:
        return 1


def is_initialized():
    return _default_group is not None


def init_parallel_env():
    """Reference: python/paddle/distributed/parallel.py:978. Multi-host TPU
    rendezvous is jax.distributed (coordination service = the TCPStore role);
    single-host it simply records the default group."""
    global _default_group
    import os
    if _default_group is not None:
        return _default_group
    coord = (os.environ.get("PADDLE_TPU_COORDINATOR")
             or os.environ.get("PADDLE_MASTER")
             or os.environ.get("MASTER_ADDR"))
    nproc = int(os.environ.get("PADDLE_TPU_NUM_PROCESSES")
                or os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    pid = int(os.environ.get("PADDLE_TPU_PROCESS_ID")
              or os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coord and nproc > 1:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nproc, process_id=pid)
    _default_group = Group(list(range(get_world_size())), axis_name=None)
    return _default_group


_group_counters: dict[tuple, int] = {}


def new_group(ranks=None, backend=None, axis_name=None):
    """Deterministic pg_id (crc32 of ranks + per-ranks creation counter):
    every process creating the same sequence of groups derives the same
    ids, so subgroup KV-mailbox collectives agree on their key prefix
    across processes (the reference assigns ring ids the same way — all
    ranks must call new_group in the same order)."""
    import zlib
    r = tuple(ranks) if ranks is not None else tuple(range(get_world_size()))
    n = _group_counters.get(r, 0)
    _group_counters[r] = n + 1
    pg_id = zlib.crc32(repr((r, n)).encode()) & 0x7FFFFFFF
    return Group(list(r), axis_name=axis_name, pg_id=pg_id)


def destroy_process_group(group=None):
    global _default_group
    _default_group = None
