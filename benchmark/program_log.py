"""The measured window, cut out of the program's own span log.

The program keeps an always-on, bounded log of spans
(``paddle_tpu/profiler/spans.py``): ``serve.step`` with its phases
``serve.plan`` / ``.assemble`` / ``.dispatch`` / ``.wait`` / ``.commit``
and the counts of what the step carried, ``serve.queue`` and
``serve.prefill`` for each request, ``train.step`` with ``train.gather``
/ ``.dispatch`` / ``.compile``. A per-layer reader is handed no object of
the program, so it reads that log, through this module:

    from benchmark import program_log

    def read(run):
        w = program_log.window(run, "serve.step")
        return w.phase_p50_ms("serve.plan") if w else None

``window`` returns None where there is nothing to read: a ``run`` without
``step_s``, a program without the log (the parent of the PR that added
it), a log without such steps. The reader then returns None and the
metric is left out of the line.

The cut is exact and needs no clock of the harness: the runners make no
engine or train step after the window closes, so the window's steps are
the LAST ``len(run["step_s"])`` records of the step's name, their phases
are the records whose ``parent_id`` is a step's ``id``, and the window on
the program's clock runs from the end of the step before the first (or
the first's start) to the end of the last. A request belongs to the
window when its first ``serve.queue`` span starts inside it; one that
still waits or prefills at the window's end enters with its age so far,
as ``ttft_p95_ms`` counts it. The steps that the profiler traced, a few
seconds of the window, are ``Window.traced_steps``.
"""
import statistics

from . import latency


def window(run, step_name):
    """The :class:`Window` of a run of the harness, or None."""
    n = len(run.get("step_s") or ())
    if not n:
        return None
    try:
        from paddle_tpu.profiler import spans
    except ImportError:         # a program from before the log
        return None
    return cut(spans.records(), spans.open_spans(), n, step_name)


def cut(records, open_records, n_steps, step_name):
    """:func:`window` over plain records (``(id, parent_id, name, t0_ns,
    t1_ns, attrs)``, oldest first; an open one has ``t1_ns`` None)."""
    at = [i for i, r in enumerate(records) if r[2] == step_name]
    if not n_steps or len(at) < n_steps:
        return None
    return Window(records, open_records, at[-n_steps:],
                  at[-n_steps - 1] if len(at) > n_steps else None)


class Window:
    """The window's steps with their phases, and its requests."""

    def __init__(self, records, open_records, at, before):
        self.steps = [records[i] for i in at]
        ids = {s[0] for s in self.steps}
        self.children = {}
        for r in records:
            if r[1] in ids:
                self.children.setdefault(r[1], []).append(r)
        self.lo_ns = self.steps[0][3] if before is None \
            else records[before][4]
        self.hi_ns = self.steps[-1][4]
        self._records, self._open = records, open_records

    # ---- the steps ----
    def step_ms(self):
        return [(s[4] - s[3]) / 1e6 for s in self.steps]

    def phase_ms(self, name):
        """Per step, the time in its children called ``name`` (a step
        may have several), in ms; None where no step has one."""
        out, seen = [], False
        for s in self.steps:
            mine = [c for c in self.children.get(s[0], ()) if c[2] == name]
            seen = seen or bool(mine)
            out.append(sum(c[4] - c[3] for c in mine) / 1e6)
        return out if seen else None

    def self_ms(self):
        """Per step, its duration less what its children cover."""
        return [(s[4] - s[3] - sum(c[4] - c[3] for c in
                                   self.children.get(s[0], ()))) / 1e6
                for s in self.steps]

    def phase_p50_ms(self, name, with_self=False):
        ms = self.phase_ms(name)
        if ms is None:
            return None
        if with_self:
            ms = [a + b for a, b in zip(ms, self.self_ms())]
        return statistics.median(ms)

    def counts(self, key, steps=None):
        """Per step, the count ``key`` it carried (0 where the step
        launched nothing and so counted nothing)."""
        return [(s[5] or {}).get(key, 0)
                for s in (self.steps if steps is None else steps)]

    def traced_steps(self, after_s, n):
        """The ``n`` steps the harness's profiler saw, for a metric that
        sets a count against a time of the device trace. ``WindowTrace``
        starts the profiler between two steps, ``after_s`` into the
        window on the clock the log has (``perf_counter``), so they are
        the first step to start that late and the ``n - 1`` after it;
        ``n`` comes from the trace. The window's start is known here to
        within the step it fell into, less than the profiler takes to
        start, which holds the first traced step back. None where the
        window does not hold ``n`` such steps."""
        t = self.lo_ns + after_s * 1e9
        i = next((i for i, s in enumerate(self.steps) if s[3] >= t), None)
        if i is None or n < 1 or i + n > len(self.steps):
            return None
        return self.steps[i:i + n]

    def share_pct(self, key, of):
        """100 x sum of ``key`` / sum of the counts named in ``of``,
        over the window's steps."""
        den = sum(sum(self.counts(k)) for k in of)
        return 100.0 * sum(self.counts(key)) / den if den else None

    def mean_ratio_pct(self, key, of):
        """Mean over the steps that carry ``of`` of 100 x key / of."""
        r = [100.0 * (s[5] or {}).get(key, 0) / s[5][of]
             for s in self.steps if (s[5] or {}).get(of)]
        return sum(r) / len(r) if r else None

    # ---- the requests ----
    def requests(self):
        """{request id: {"queue_ms", "prefill_ms", "chunks"}} of the
        requests enqueued inside the window; spans still open at its end
        count up to it."""
        first, out = {}, {}
        spans = [r for r in self._records + list(self._open)
                 if r[2] in ("serve.queue", "serve.prefill")]
        for r in sorted(spans, key=lambda r: r[3]):
            rid = (r[5] or {}).get("request")
            if r[2] == "serve.queue":
                first.setdefault(rid, r[3])
            if rid not in first or \
                    not self.lo_ns <= first[rid] <= self.hi_ns:
                continue
            t1 = self.hi_ns if r[4] is None else min(r[4], self.hi_ns)
            d = out.setdefault(rid, {"queue_ms": 0.0, "prefill_ms": 0.0,
                                     "chunks": 0})
            d["queue_ms" if r[2] == "serve.queue" else "prefill_ms"] += \
                max(0, t1 - r[3]) / 1e6
            d["chunks"] += (r[5] or {}).get("chunks", 0)
        return out

    def request_p95(self, key):
        vals = [d[key] for d in self.requests().values()]
        return latency.percentile(vals, 95) if vals else None
