"""Async input pipeline: device prefetching + pipeline metrics.

The training hot path used to run host and TPU in lockstep: the loader
yielded host-resident batches whose H2D transfer serialized into each
step's dispatch (the input/dispatch stall PAPERS.md's Gemma-on-TPU
comparison blames for most of the GPU->TPU MFU gap). This module overlaps
the three phases:

- collation runs in the DataLoader's existing worker pool (threads or
  processes — ``io/worker.py``);
- ``DevicePrefetchIterator`` stages the next ``prefetch_factor`` batches
  onto the device in a background thread (``jax.device_put`` is an async
  dispatch under PJRT, so staging batch N+1 overlaps computing batch N);
- staged Tensor leaves are marked donatable so ``jit.TrainStep`` can give
  their buffers back to XLA (the batch is consumed exactly once).

``PIPELINE_METRICS`` mirrors serving/metrics.py: a ``snapshot()`` dict
(``input_stall_ms``, ``steps_in_flight``, ``step_dispatches``; read by
tests/test_async_pipeline.py) plus instant events on the native profiler timeline when one is recording.
"""
from __future__ import annotations

import queue
import threading
import time
import weakref

import jax

from ..core import native as _nv
from ..core.tensor import Tensor


class PipelineMetrics:
    """Counters/gauges for the async input pipeline.

    Same two consumers as ServingMetrics: ``snapshot()`` rides the bench
    artifact; updates emit ``pipeline.*`` instants through the native
    recorder so input stalls land on the chrome-trace timeline next to op
    spans and serving gauges.
    """

    def __init__(self):
        self.reset()

    def reset(self):
        self.batches_staged = 0
        self.input_stall_ms = 0.0
        self.steps_in_flight = 0
        self.max_steps_in_flight = 0
        self.step_dispatches = 0

    def record_staged(self):
        self.batches_staged += 1

    def record_stall(self, ms):
        self.input_stall_ms += float(ms)
        if _nv.prof_enabled():
            _nv.prof_instant(f"pipeline.input_stall_ms={ms:.3f}", 3)

    def set_in_flight(self, n):
        self.steps_in_flight = int(n)
        self.max_steps_in_flight = max(self.max_steps_in_flight, int(n))
        if _nv.prof_enabled():
            _nv.prof_instant(f"pipeline.steps_in_flight={n}", 3)

    def record_dispatch(self):
        self.step_dispatches += 1

    def snapshot(self) -> dict:
        return {
            "batches_staged": self.batches_staged,
            "input_stall_ms": self.input_stall_ms,
            "steps_in_flight": self.steps_in_flight,
            "max_steps_in_flight": self.max_steps_in_flight,
            "step_dispatches": self.step_dispatches,
        }


PIPELINE_METRICS = PipelineMetrics()


class _WorkerError:
    """Wraps a producer/stager-thread exception for re-raise in the
    consumer (a plain tuple sentinel would hit Tensor.__eq__ on tensor
    batches). Shared with the DataLoader thread producer (io/__init__)."""

    def __init__(self, exc):
        self.exc = exc


_SENTINEL = object()


class DevicePrefetchIterator:
    """Stage batches onto the device ahead of consumption.

    Wraps any iterator/iterable of batches (pytrees with Tensor leaves —
    a DataLoader, a generator, a list). A background thread pulls batches,
    re-homes every Tensor leaf with ``jax.device_put`` onto ``device``
    (None = default device, uncommitted, so multi-device programs keep
    placement freedom), and keeps up to ``prefetch_factor`` staged batches
    in a bounded queue. Non-Tensor leaves pass through untouched.

    Staged Tensors carry ``_staged_h2d=True``: the pipeline owns them and
    yields each exactly once, so ``jit.TrainStep`` may donate their
    buffers back to XLA.

    ``FLAGS_async_pipeline=False`` degrades to a synchronous passthrough
    (same staging, no thread, no buffering) so the whole pipeline runs on
    one debuggable path.
    """

    def __init__(self, it, prefetch_factor=2, device=None,
                 mark_donatable=True, metrics=None):
        from ..core.flags import GLOBAL_FLAGS
        self._src = iter(it)
        self._device = device
        self._metrics = metrics if metrics is not None else PIPELINE_METRICS
        self._size = max(1, int(prefetch_factor))
        self._async = bool(GLOBAL_FLAGS.get("async_pipeline"))
        # the FLAGS_async_pipeline=False kill-switch must disarm the WHOLE
        # feature: the sync passthrough neither threads nor marks batches
        # donatable, so TrainStep never donates on the bisect path
        self._mark = mark_donatable and self._async
        self._stop = threading.Event()
        self._done = False
        if self._async:
            self._q: queue.Queue = queue.Queue(maxsize=self._size)
            # The stager holds only a WEAK reference to this iterator: an
            # abandoned iterator (no close()) gets collected, the weakref
            # dies, and the thread exits instead of parking forever in
            # q.put with the staged batches pinned.
            self._thread = threading.Thread(
                target=_stager_loop,
                args=(weakref.ref(self), self._stop, self._q),
                daemon=True, name="paddle_tpu-device-prefetch")
            self._thread.start()
            self._finalizer = weakref.finalize(self, self._stop.set)

    # ---- staging ----
    def _stage(self, batch):
        def put(x):
            if not isinstance(x, Tensor):
                return x
            t = Tensor(jax.device_put(x._data, self._device))
            if self._mark:
                t._staged_h2d = True
            return t

        out = jax.tree.map(put, batch,
                           is_leaf=lambda x: isinstance(x, Tensor))
        self._metrics.record_staged()
        return out

    # ---- consumption ----
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if not self._async:
            try:
                return self._stage(next(self._src))
            except StopIteration:
                self._done = True
                raise
        t0 = time.perf_counter()
        item = self._q.get()
        self._metrics.record_stall((time.perf_counter() - t0) * 1e3)
        if item is _SENTINEL:
            self._done = True
            raise StopIteration
        if isinstance(item, _WorkerError):
            self._done = True
            raise item.exc
        return item

    def close(self):
        """Stop the stager and release the source (early consumer exit)."""
        self._stop.set()
        self._done = True
        if self._async:
            while True:  # unblock a stager parked on a full queue
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    break
        src_close = getattr(self._src, "close", None)
        if src_close is not None and not self._async:
            # async mode: the stager thread owns the generator frame;
            # closing it from here would race the in-progress next()
            try:
                src_close()
            except Exception:
                pass


def _stager_loop(wself, stop, q):
    """Module-level stager body: touches the iterator only through the
    weakref, dropping the strong ref before every blocking put."""
    try:
        while not stop.is_set():
            it = wself()
            if it is None:
                return
            try:
                b = next(it._src)
            except StopIteration:
                del it
                _put_staged(q, _SENTINEL, stop, wself)
                return
            item = it._stage(b)
            del it
            if not _put_staged(q, item, stop, wself):
                return
    except BaseException as e:  # propagate into the consumer
        try:
            q.put_nowait(_WorkerError(e))
        except queue.Full:
            try:  # full queue + dead consumer: trade one batch for the error
                q.get_nowait()
                q.put_nowait(_WorkerError(e))
            except (queue.Empty, queue.Full):
                pass


def _put_staged(q, item, stop, wself):
    while True:
        if stop.is_set() or wself() is None:
            return False
        try:
            q.put(item, timeout=0.2)
            return True
        except queue.Full:
            continue


__all__ = ["DevicePrefetchIterator", "PipelineMetrics", "PIPELINE_METRICS"]
