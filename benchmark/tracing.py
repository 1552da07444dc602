"""A few seconds of profiler trace inside a measured window, and the
host spans that name what the loop was doing."""
import shutil
import tempfile
import time

import jax

from . import xplane


def span(name):
    """A host span on the profiler's clock; free when no trace runs."""
    return jax.profiler.TraceAnnotation(name)


class WindowTrace:
    """Starts the profiler ``after_s`` into the window and stops it
    ``length_s`` later; the loop calls :meth:`poll` between its steps.
    The traced part is wrapped in the span ``bench.trace_window``."""

    def __init__(self, enabled, after_s, length_s, keep_dir=None):
        self.enabled = enabled
        self.after_s, self.length_s = after_s, length_s
        self.keep_dir = keep_dir
        self._dir = None
        self._window = None
        #: seconds the loop stood still while the profiler started and
        #: stopped inside it; a rate taken in a traced run leaves them out
        self.overhead_s = 0.0
        self.state = "off" if not enabled else "waiting"

    def poll(self, since_open_s):
        t0, before = time.perf_counter(), self.state
        self._poll(since_open_s)
        if self.state != before:
            self.overhead_s += time.perf_counter() - t0

    def _poll(self, since_open_s):
        if self.state == "waiting" and since_open_s >= self.after_s:
            self._dir = self.keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans are our own
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._window = span("bench.trace_window")
            self._window.__enter__()
            self.state = "on"
        elif self.state == "on" and \
                since_open_s >= self.after_s + self.length_s:
            self.stop()

    def stop(self):
        if self.state == "on":
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def events(self):
        """The trace as plain events, or None when none was taken."""
        self.stop()
        if self.state != "done":
            return None
        try:
            path = xplane.find_xplane(self._dir)
            return xplane.load(path) if path else None
        finally:
            if not self.keep_dir:
                shutil.rmtree(self._dir, ignore_errors=True)
