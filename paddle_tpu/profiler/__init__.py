"""paddle_tpu.profiler — host + device profiling.

TPU-native analog of the reference's profiler stack
(reference: python/paddle/profiler/profiler.py:358 Profiler with
wait/warmup/active scheduler; RecordEvent API profiler/utils.py; C++ host
tracer paddle/fluid/platform/profiler/host_tracer.cc; CUPTI device tracer
cuda_tracer.cc; chrome-trace export chrometracing_logger.cc; stats tables
profiler_statistic.py).

Mapping onto this stack:
- the program's own spans -> ``spans.span`` (profiler/spans.py): the one
  span primitive, always on; each span goes to ``jax.profiler``'s
  timeline (the clock of the device lines) and to one bounded in-memory
  log. ``compile_event`` is a thin wrapper over it;
- per-op host spans -> the native C++ event recorder
  (core/native/csrc/profiler.cc) with per-op hooks in the eager dispatch,
  on only while a ``Profiler`` records; ``compile_event`` and the user's
  ``RecordEvent`` feed it too;
- device side -> jax.profiler (XLA xplane; the TPU equivalent of CUPTI),
  started/stopped alongside when ``targets`` includes ProfilerTarget.TPU;
- export -> chrome://tracing JSON (host) + TensorBoard xplane dir (device);
- ``summary()`` -> per-op host time table like profiler_statistic.py.

Serving observability rides the same host timeline: ``serving.*``
gauge instants (serving/metrics.py) and ``trace.*`` request-span
instants (serving/tracing.py) land next to op spans while a Profiler
records, and ``RequestTracer.export_chrome_trace(telemetry=Scraper)``
merges op spans, request spans, and the fleet-telemetry counter lane
(paddle_tpu.telemetry) into ONE chrome://tracing view —
docs/OBSERVABILITY.md is the consolidated guide.
"""
from __future__ import annotations

import enum
import os
from collections import defaultdict

from jax.profiler import TraceAnnotation

from ..core import dispatch as _dispatch
from ..core import native as _nv
from . import phases, spans
from .spans import span


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1   # accepted for API parity; no-op on this stack
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Step-state schedule (reference: profiler.py make_scheduler)."""

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = closed + ready + record
        if repeat and s >= cycle * repeat:
            return ProfilerState.CLOSED
        pos = s % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


class RecordEvent:
    """User span (reference: paddle.profiler.RecordEvent), on both
    timelines: ``jax.profiler``'s, beside the device lines, while a trace
    runs, and the native recorder's while a ``Profiler`` records. Free
    otherwise, and not in the span log: that ring is sized for the
    program's own ``serve.*`` / ``train.*`` spans, which a user's loop
    of events must not push out."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._ann = None
        self._tok = 0

    def begin(self):
        self._tok = _nv.prof_begin(self.name, 2)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        _nv.prof_end(self._tok)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """``with Profiler(targets=[...]) as p: ... p.step()`` (reference:
    python/paddle/profiler/profiler.py:358)."""

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False):
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        if scheduler is None:
            self.scheduler = lambda step: ProfilerState.RECORD
        elif isinstance(scheduler, tuple):
            start, end = scheduler
            self.scheduler = lambda step: (
                ProfilerState.RECORD if start <= step < end
                else ProfilerState.CLOSED)
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._device_tracing = False
        self._device_dir = None

    # ---- lifecycle ----
    def start(self):
        self._apply_state(self.scheduler(self.step_num))

    def stop(self):
        self._apply_state(ProfilerState.CLOSED)
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self, num_samples=None):
        self.step_num += 1
        _nv.prof_instant(f"profiler_step#{self.step_num}", 3)
        if _nv.prof_enabled():
            # async-pipeline gauges land next to op spans and serving
            # gauges at each step mark (io/prefetch.py)
            from ..io.prefetch import PIPELINE_METRICS as _pm
            # _total: the running accumulator — per-stall deltas go out
            # as pipeline.input_stall_ms from record_stall, a different
            # quantity that must not share the label
            _nv.prof_instant(
                f"pipeline.input_stall_ms_total={_pm.input_stall_ms:.3f}",
                3)
            _nv.prof_instant(
                f"pipeline.steps_in_flight={_pm.steps_in_flight}", 3)
        self._apply_state(self.scheduler(self.step_num))

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def _apply_state(self, state):
        recording = state in (ProfilerState.RECORD,
                              ProfilerState.RECORD_AND_RETURN)
        was = self.current_state in (ProfilerState.RECORD,
                                     ProfilerState.RECORD_AND_RETURN)
        if recording and not was:
            self._begin_record()
        elif was and not recording:
            self._end_record()
        self.current_state = state

    def _begin_record(self):
        _nv.ensure_loaded()
        if not self.timer_only:
            _nv.prof_enable(True)
            _dispatch.PROFILE_HOOK = (lambda name: _nv.prof_begin(name, 1),
                                      _nv.prof_end)
        if ProfilerTarget.TPU in self.targets and not self.timer_only:
            try:
                import jax
                self._device_dir = os.environ.get(
                    "PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_xplane")
                jax.profiler.start_trace(self._device_dir)
                self._device_tracing = True
            except Exception:
                self._device_tracing = False

    def _end_record(self):
        _dispatch.PROFILE_HOOK = None
        _nv.prof_enable(False)
        if self._device_tracing:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._device_tracing = False

    # ---- export / stats ----
    def export_chrome_tracing(self, dir_name, worker_name=None):
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(dir_name,
                            f"{worker_name or 'host'}.pt.trace.json")
        _nv.prof_dump_chrome(path)
        return path

    export = export_chrome_tracing

    def events(self):
        return _nv.prof_export()

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Per-op host time table (reference: profiler_statistic.py).
        ``sorted_by`` accepts a SortedKeys enum or "total"/"avg"/"max";
        GPU* keys alias CPU* on the host-event tier. ``views`` accepts
        SummaryView values for API parity (the host tier renders the
        operator view)."""
        if hasattr(sorted_by, "name"):  # SortedKeys
            sorted_by = {"Total": "total", "Avg": "avg", "Max": "max",
                         "Min": "min"}[
                sorted_by.name.replace("CPU", "").replace("GPU", "")]
        # name -> [calls, total_ns, max_ns, min_ns]
        agg = defaultdict(lambda: [0, 0.0, 0.0, float("inf")])
        for name, tid, start, dur, cat in _nv.prof_export():
            if cat != 1:
                continue
            a = agg[name]
            a[0] += 1
            a[1] += dur
            a[2] = max(a[2], dur)
            a[3] = min(a[3], dur)
        keyfn = {"total": lambda kv: -kv[1][1],
                 "avg": lambda kv: -kv[1][1] / max(kv[1][0], 1),
                 "max": lambda kv: -kv[1][2],
                 "min": lambda kv: kv[1][3]}[sorted_by]
        rows = sorted(agg.items(), key=keyfn)
        unit = {"ms": 1e6, "us": 1e3, "ns": 1.0, "s": 1e9}[time_unit]
        lines = [f"{'Op':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                 f"{'Avg':>12}{'Max':>12}{'Min':>12}"]
        lines.append("-" * 98)
        for name, (calls, total, mx, mn) in rows:
            lines.append(f"{name:<40}{calls:>8}{total / unit:>14.3f}"
                         f"{total / unit / max(calls, 1):>12.3f}"
                         f"{mx / unit:>12.3f}{mn / unit:>12.3f}")
        table = "\n".join(lines)
        print(table)
        return {name: {"calls": c, "total_ns": t, "max_ns": m, "min_ns": mn}
                for name, (c, t, m, mn) in rows}


def export_chrome_tracing(dir_name, worker_name=None):
    """Standalone on_trace_ready factory (reference API)."""

    def handler(prof):
        prof.export_chrome_tracing(dir_name, worker_name)

    return handler


class compile_event:
    """A :func:`spans.span` marking a compilation (trace + lower +
    build) that also feeds the native recorder, under the one ``name``.

    Used by ``jit.TrainStep`` around each first-call trace so recompiles
    caused by shape / flag changes show up next to the pipeline gauges
    instead of masquerading as one silently slow step. ``.ms`` carries
    the measured wall time after exit (dispatch of the compiled call is
    synchronous through tracing/lowering; execution stays async, so the
    span measures compilation, not the step)."""

    def __init__(self, name):
        self.name = name
        self.ms = None
        self._span = span(name)
        self._tok = 0

    def __enter__(self):
        self._tok = _nv.prof_begin(self.name, 2)
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self._span.end()
        self.ms = (self._span.t1_ns - self._span.t0_ns) / 1e6
        _nv.prof_end(self._tok)
        return False


__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing", "compile_event",
           "span", "spans"]


class SortedKeys(enum.Enum):
    """Sort order for ``Profiler.summary`` (reference:
    profiler_statistic.py:49). GPU* keys map to device-view sorting when
    device events exist; on this host-event tier they alias CPU*."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.Enum):
    """Summary views (reference: profiler.py:55). The host-event tier
    renders Operator/Overview; the device timeline lives in the xplane
    trace (export via jax.profiler, see Profiler device_tracing)."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name, worker_name=None):
    """on_trace_ready factory writing the protobuf artifact (reference:
    profiler.py:280; schema proto/profiler_result.proto here)."""

    def handler(prof):
        import socket
        os.makedirs(dir_name, exist_ok=True)
        from .proto import profiler_result_pb2 as pb
        name = worker_name or f"{socket.gethostname()}_{os.getpid()}"
        result = pb.ProfilerResult(host=socket.gethostname(),
                                   pid=os.getpid())
        for ev_name, tid, start, dur, cat in prof.events():
            e = result.events.add()
            e.name, e.tid = ev_name, int(tid)
            e.start_ns, e.dur_ns = int(start), int(dur)
            e.category = int(cat)
        path = os.path.join(dir_name, f"{name}.pb")
        with open(path, "wb") as f:
            f.write(result.SerializeToString())
        return path

    return handler


def load_profiler_result(filename):
    """Load an ``export_protobuf`` artifact (reference: utils.py:161).
    Returns the event tuples in ``Profiler.events()`` order."""
    from .proto import profiler_result_pb2 as pb
    result = pb.ProfilerResult()
    with open(filename, "rb") as f:
        result.ParseFromString(f.read())
    return [(e.name, e.tid, e.start_ns, e.dur_ns, e.category)
            for e in result.events]


__all__ += ["SortedKeys", "SummaryView", "export_protobuf",
            "load_profiler_result"]
