"""The program's one span primitive: every span goes to the profiler's
timeline AND to one bounded, always-on, in-memory log.

    with span("serve.step", engine=3) as sp:      # nests on this thread
        sp.phase("serve.plan")                    # sequential children
        ...
        sp.phase("serve.dispatch")
        ...
        sp.phase(None)                            # the rest is self time
        sp.set(rows=17)                           # counts known later
    life = begin("serve.queue", request="r1")     # crosses steps
    ...
    life.end()

A NESTED span (``with``, or a ``phase`` of one) is two clock reads, one
``jax.profiler.TraceAnnotation`` enter/exit and one ``deque.append``.
The annotation is an atomic check and nothing else while no profiler
session runs; in a session the span lands on ``/host:CPU`` on the same
clock as the device's ``XLA Ops`` line, carrying the attributes known at
enter. The log's record carries every attribute, including those set
while the span was open.

A DETACHED span (``begin`` ... ``end``) is for intervals that cross steps
and overlap one another, a request's wait in the queue or its prefill:
the profiler's timeline expects the spans of one thread to nest, so
these go to the log only. While open they are listed by
:func:`open_spans`.

The log is process-wide on purpose: a reader that was handed no object
(the benchmark's per-layer readers, an operator's console) imports this
module and reads. It needs no switch, so it has none. It holds the last
``LOG_CAPACITY`` = 2**17 records, a 45 s window of 2 ms steps with their
children; older records fall off. A child's record is about 0.2 KB
and a serving step's with its seven counts about 0.6 KB: a full log of
serving steps holds 33 MB, of train steps 24 MB (``tracemalloc``,
CPython 3.12), and a process reaches that only after 22,000 serving or
44,000 train steps.

A record is ``Record(id, parent_id, name, t0_ns, t1_ns, attrs)``: times
from ``time.perf_counter_ns()``, ``parent_id`` the enclosing nested span
of the same thread (0: none), ``attrs`` a small dict of ints, floats and
strings or None. The spans of one request share ``attrs["request"]``.
"""
from __future__ import annotations

import itertools
import threading
import time
import weakref
from collections import deque, namedtuple

from jax.profiler import TraceAnnotation

LOG_CAPACITY = 1 << 17

Record = namedtuple("Record", "id parent_id name t0_ns t1_ns attrs")

_clock = time.perf_counter_ns
_log: deque = deque(maxlen=LOG_CAPACITY)
#: detached spans not ended yet; weak, so a span whose owner is gone
#: (an engine dropped with requests waiting) leaves with it
_open = weakref.WeakValueDictionary()
_ids = itertools.count(1)
_tls = threading.local()


def _stack() -> list:
    try:
        return _tls.stack
    except AttributeError:
        _tls.stack = []
        return _tls.stack


class span:
    """One interval with a name. ``with span(...)`` nests it under the
    span open on this thread; :func:`begin` starts a detached one."""

    __slots__ = ("name", "attrs", "id", "parent_id", "t0_ns", "t1_ns",
                 "_ann", "_stack", "_depth", "_phase", "__weakref__")

    def __init__(self, name, **attrs):
        self.name = name
        self.attrs = attrs
        self.t0_ns = self.t1_ns = self._ann = self._phase = None

    def __enter__(self):
        stack = self._stack = _stack()
        self._depth = len(stack)
        self.parent_id = stack[-1] if stack else 0
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0_ns = _clock()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def set(self, **attrs):
        """Counts known only after enter; they reach the log's record."""
        self.attrs.update(attrs)

    def phase(self, name):
        """End the phase that runs and start ``name`` as this span's next
        child; ``None`` starts none, and the time until the next phase is
        this span's own. The children of a span cut into phases follow
        one another, so they sum to no more than it."""
        if self._phase is not None:
            self._phase.end()
        self._phase = None if name is None else span(name).__enter__()

    def end(self, t_ns=None):
        """Close the span (once: a second call does nothing) and append
        its record. ``t_ns`` gives the instant where the caller has read
        the clock already, so that one span ends where the next begins.
        """
        if self.t1_ns is not None or self.t0_ns is None:
            return
        if self._phase is not None:
            self._phase.end()
        self.t1_ns = _clock() if t_ns is None else t_ns
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            # also drops what an exception left open below this span
            del self._stack[self._depth:]
        else:
            _open.pop(self.id, None)
        # a plain tuple: a third of a Record's cost; readers get Records
        _log.append((self.id, self.parent_id, self.name, self.t0_ns,
                     self.t1_ns, self.attrs or None))


def begin(name, t_ns=None, **attrs) -> span:
    """Start a detached span: log only, no parent, ended by ``.end()``
    from anywhere."""
    sp = span(name, **attrs)
    sp.id, sp.parent_id = next(_ids), 0
    sp.t0_ns = _clock() if t_ns is None else t_ns
    _open[sp.id] = sp
    return sp


def records(name=None) -> list:
    """A copy of the log, oldest first; ``name`` keeps one name."""
    return [Record._make(r) for r in list(_log)
            if name is None or r[2] == name]


def open_spans(name=None) -> list:
    """Detached spans still open, as records whose ``t1_ns`` is None."""
    return [Record(s.id, 0, s.name, s.t0_ns, None, dict(s.attrs))
            for s in sorted(list(_open.values()), key=lambda s: s.id)
            if name is None or s.name == name]


def clear():
    """Forget every record (tests, and a console that starts a window)."""
    _log.clear()


def _percentile(sorted_vals, q):
    i = q / 100.0 * (len(sorted_vals) - 1)
    lo = int(i)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (i - lo)


def summary(prefix=None, last=None, **tags) -> dict:
    """The operator's view: per name ``count``, ``total_ms``, ``p50_ms``,
    ``p95_ms`` and ``self_ms`` (duration less what child spans cover)
    over the newest ``last`` records (all of them by default), names
    cut to those that start with ``prefix``; ``tags`` keeps the spans
    that carry these attributes and their children (one replica's steps
    out of a process of several). Children are appended before their
    parent, so a cut through a step charges the cut-off children's time
    to that one parent's self time. Safe to call while other threads
    open spans: the log is copied in one step."""
    recs = list(_log)
    if last is not None:
        recs = recs[-last:]
    if tags:
        want = tags.items()
        mine = {r[0] for r in recs if r[5] and want <= r[5].items()}
        recs = [r for r in recs if r[0] in mine or r[1] in mine]
    covered, by = {}, {}
    for rid, parent_id, name, t0_ns, t1_ns, _ in recs:
        dur = t1_ns - t0_ns
        if parent_id:
            covered[parent_id] = covered.get(parent_id, 0) + dur
        if prefix is None or name.startswith(prefix):
            d, s = by.setdefault(name, ([], [0]))
            d.append(dur)
            s[0] += dur - covered.pop(rid, 0)
    out = {}
    for name, (durs, self_ns) in sorted(by.items()):
        durs.sort()
        out[name] = {"count": len(durs), "total_ms": sum(durs) / 1e6,
                     "p50_ms": _percentile(durs, 50) / 1e6,
                     "p95_ms": _percentile(durs, 95) / 1e6,
                     "self_ms": self_ns[0] / 1e6}
    return out


__all__ = ["LOG_CAPACITY", "Record", "span", "begin", "records",
           "open_spans", "summary", "clear"]
