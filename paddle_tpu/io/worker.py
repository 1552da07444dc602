"""Process-based DataLoader workers (reference:
python/paddle/io/dataloader/worker.py _worker_loop + dataloader_iter.py
_DataLoaderIterMultiProcess).

Workers are REAL processes (spawn), so Python-bound augmentation pipelines
scale past the GIL — the round-2 verdict's DataLoader gap. Transport is
the multiprocessing queue (pipe); tensors are converted to numpy for the
wire and re-materialized in the parent, so a worker never initializes a
device backend (it forces JAX onto the CPU on startup — a chip belongs to
one process at a time, and that process is the trainer).

Ordering contract matches the reference: batches are re-assembled in
sampler order in the parent (out-of-order results are buffered).
``worker_init_fn(worker_id)`` runs in the worker before the first batch;
``get_worker_info()`` exposes (id, num_workers, dataset) inside workers.
"""
from __future__ import annotations

import contextlib
import multiprocessing as mp
import os
import queue as _queue
import time as _time

import numpy as np


@contextlib.contextmanager
def _safe_spawn_env():
    """Set the no-device env in the PARENT around Process.start(): spawn
    children re-import the main module (and unpickle jax-touching args)
    BEFORE the worker target runs, so only inherited environment reliably
    prevents a worker from initializing the TPU backend."""
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved


class WorkerInfo:
    def __init__(self, wid, num_workers, dataset, seed=0):
        self.id = wid
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed

    def __repr__(self):
        return (f"WorkerInfo(id={self.id}, "
                f"num_workers={self.num_workers})")


_worker_info: WorkerInfo | None = None


def get_worker_info():
    """Inside a worker process: this worker's info; None in the parent
    (reference: python/paddle/io/dataloader/worker.py get_worker_info)."""
    return _worker_info


def _encode(obj):
    """Tensor/jax leaves -> numpy for pipe transport."""
    from ..core.tensor import Tensor

    if isinstance(obj, Tensor):
        return _WireTensor(np.asarray(obj.numpy()))
    if type(obj).__module__.startswith("jaxlib") or \
            type(obj).__name__ == "ArrayImpl":
        return _WireTensor(np.asarray(obj))
    if isinstance(obj, tuple):
        return tuple(_encode(o) for o in obj)
    if isinstance(obj, list):
        return [_encode(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _encode(v) for k, v in obj.items()}
    return obj


class _WireTensor:
    """Private wire wrapper for device arrays crossing the worker queue.

    A wrapper class (not a tagged tuple) so a dataset that legitimately
    yields ("__tensor__", ...) tuples round-trips unchanged."""

    __slots__ = ("array",)

    def __init__(self, array):
        self.array = array


def _decode(obj):
    from ..core.tensor import Tensor

    if isinstance(obj, _WireTensor):
        return Tensor(obj.array)
    if isinstance(obj, tuple):
        return tuple(_decode(o) for o in obj)
    if isinstance(obj, list):
        return [_decode(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _decode(v) for k, v in obj.items()}
    return obj


class _Err:
    """Carries only the FORMATTED error: shipping the live exception object
    can fail to pickle in the queue's feeder thread, silently losing the
    item and deadlocking the parent."""

    def __init__(self, exc):
        import traceback
        self.tb = "".join(traceback.format_exception(exc)).strip()


def _worker_loop(dataset, index_q, result_q, collate_fn, wid, num_workers,
                 init_fn, base_seed):
    # a dataset worker must NEVER take the chip: force cpu
    os.environ["JAX_PLATFORMS"] = "cpu"
    global _worker_info
    _worker_info = WorkerInfo(wid, num_workers, dataset, base_seed + wid)
    np.random.seed((base_seed + wid) % (2 ** 31))
    try:
        if init_fn is not None:
            init_fn(wid)
    except BaseException as e:  # noqa: BLE001 — reported to the parent
        result_q.put((-1, -1, _Err(e)))
        return
    while True:
        item = index_q.get()
        if item is None:
            return
        epoch, seq, idxs = item
        try:
            batch = collate_fn([dataset[i] for i in idxs])
            result_q.put((epoch, seq, _encode(batch)))
        except BaseException as e:  # noqa: BLE001
            result_q.put((epoch, seq, _Err(e)))


def _iterable_worker_loop(dataset, result_q, collate_fn, wid, num_workers,
                          init_fn, base_seed, batch_size, drop_last):
    """IterableDataset: each worker iterates its own copy; the user shards
    via get_worker_info() (the reference contract). Batches are tagged
    (worker, k) — order across workers is arbitrary, as in the reference."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    global _worker_info
    _worker_info = WorkerInfo(wid, num_workers, dataset, base_seed + wid)
    np.random.seed((base_seed + wid) % (2 ** 31))
    try:
        if init_fn is not None:
            init_fn(wid)
        buf = []
        for sample in dataset:
            buf.append(sample)
            if len(buf) == batch_size:
                result_q.put((0, _encode(collate_fn(buf))))
                buf = []
        if buf and not drop_last:
            result_q.put((0, _encode(collate_fn(buf))))
        result_q.put((None, wid))   # this worker is done
    except BaseException as e:  # noqa: BLE001
        result_q.put((0, _Err(e)))
        result_q.put((None, wid))


class _ProcessPool:
    """Worker pool for one DataLoader (persistent across epochs when
    persistent_workers=True)."""

    def __init__(self, loader):
        self.loader = loader
        self.epoch = 0
        ctx = mp.get_context("spawn")
        self.index_q = ctx.Queue()
        self.result_q = ctx.Queue()
        base_seed = int.from_bytes(os.urandom(2), "little")
        self.procs = []
        with _safe_spawn_env():
            for wid in range(loader.num_workers):
                p = ctx.Process(
                    target=_worker_loop,
                    args=(loader.dataset, self.index_q, self.result_q,
                          loader.collate_fn, wid, loader.num_workers,
                          loader.worker_init_fn, base_seed),
                    daemon=True)
                p.start()
                self.procs.append(p)

    def run_epoch(self, idx_batches, timeout):
        """Feed every index batch, yield collated results in order.

        Items carry an epoch tag: an abandoned epoch (early ``break`` on a
        persistent pool) leaves stale work in the queues, which the next
        epoch discards instead of mistaking for its own batches."""
        self.epoch += 1
        epoch = self.epoch
        inflight = 0
        last_progress = _time.monotonic()
        pending = {}
        next_out = 0
        it = iter(enumerate(idx_batches))
        exhausted = False
        depth = self.loader.num_workers * self.loader.prefetch_factor
        while True:
            while not exhausted and inflight < depth:
                try:
                    seq, idxs = next(it)
                except StopIteration:
                    exhausted = True
                    break
                self.index_q.put((epoch, seq, list(idxs)))
                inflight += 1
            if inflight == 0:
                return
            wait_step = min(timeout, 5.0) if timeout else 5.0
            try:
                # bounded waits so a dead worker is detected rather than
                # blocking forever (the reference's _thread_monitor role)
                ep, seq, payload = self.result_q.get(timeout=wait_step)
            except _queue.Empty:
                if not self.alive():
                    self.shutdown()
                    raise RuntimeError(
                        "DataLoader worker died unexpectedly (killed or "
                        "crashed without reporting)")
                if timeout and _time.monotonic() - last_progress >= timeout:
                    self.shutdown()
                    raise RuntimeError(
                        f"DataLoader worker timed out after {timeout}s")
                continue
            if isinstance(payload, _Err):
                # errors surface regardless of epoch tag (an init-fn
                # failure is tagged -1; dropping it would hide the trace)
                self.shutdown()
                raise RuntimeError(
                    f"DataLoader worker failed: {payload.tb}")
            if timeout and _time.monotonic() - last_progress >= timeout:
                # wall-clock deadline (monotonic): stale-epoch results
                # consume real time and must not postpone the timeout
                self.shutdown()
                raise RuntimeError(
                    f"DataLoader worker timed out after {timeout}s")
            if ep != epoch:
                continue   # stale result from an abandoned epoch
            last_progress = _time.monotonic()  # current-epoch progress
            inflight -= 1
            pending[seq] = payload
            while next_out in pending:
                yield _decode(pending.pop(next_out))
                next_out += 1

    def shutdown(self):
        for _ in self.procs:
            try:
                self.index_q.put(None)
            except Exception:
                pass
        for p in self.procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        self.procs = []

    def alive(self):
        return bool(self.procs) and all(p.is_alive() for p in self.procs)


def iter_iterable_multiprocess(loader, timeout):
    """One epoch over an IterableDataset with worker processes."""
    ctx = mp.get_context("spawn")
    result_q = ctx.Queue()
    base_seed = int.from_bytes(os.urandom(2), "little")
    procs = []
    with _safe_spawn_env():
        for wid in range(loader.num_workers):
            p = ctx.Process(
                target=_iterable_worker_loop,
                args=(loader.dataset, result_q, loader.collate_fn, wid,
                      loader.num_workers, loader.worker_init_fn, base_seed,
                      loader.batch_size, loader.drop_last),
                daemon=True)
            p.start()
            procs.append(p)
    done = 0
    last_progress = _time.monotonic()
    try:
        while done < len(procs):
            try:
                tag, payload = result_q.get(
                    timeout=min(timeout, 5.0) if timeout else 5.0)
                last_progress = _time.monotonic()
            except _queue.Empty:
                dead = sum(not p.is_alive() for p in procs)
                if dead > done:   # a worker died without its done sentinel
                    raise RuntimeError(
                        "DataLoader worker died unexpectedly (killed or "
                        "crashed without reporting)")
                if timeout and _time.monotonic() - last_progress >= timeout:
                    raise RuntimeError(
                        f"DataLoader worker timed out after {timeout}s")
                continue
            if tag is None:
                done += 1
                from ..core.flags import GLOBAL_FLAGS
                if done and GLOBAL_FLAGS.get(
                        "enable_exit_when_partial_worker"):
                    # uneven shards: the epoch ends when the FIRST worker
                    # runs dry, so no rank spins on a longer shard
                    # (reference FLAGS_enable_exit_when_partial_worker)
                    return
                continue
            if isinstance(payload, _Err):
                raise RuntimeError(
                    f"DataLoader worker failed: {payload.tb}")
            yield _decode(payload)
    finally:
        for p in procs:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()


__all__ = ["get_worker_info", "WorkerInfo", "_ProcessPool",
           "iter_iterable_multiprocess"]
