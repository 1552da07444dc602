"""From a profiler trace (``*.xplane.pb``) to device busy time, idle
gaps and per-operation time.

Two steps, so the arithmetic can be checked on a small recorded piece:
:func:`load` turns the file into plain events, :func:`reduce` turns
events into numbers. Events are ``[name, start_ns, duration_ns]``.

What a v5e trace looks like (looked at by hand, PR 25): one plane per
chip named ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's whole text (a
Pallas kernel's instruction is named after the ``name=`` its
``pallas_call`` carries: ``%ragged_paged_attention.12 = ...``); a
``while`` has an event of its own that spans its body's, so per-name
times may overlap while the busy union does not. The line ``XLA
Modules`` holds one event per executable run. Host threads are lines of
the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear
there under their own names, on the same clock as the device lines: the
benchmark's ``bench.*`` around its calls into the program, and inside
them the program's own (``serve.*``, ``train.*``;
``paddle_tpu/profiler/spans.py``), nested.
"""
import bisect
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the benchmark's spans and the program's
SPAN_PREFIXES = ("bench.", "serve.", "train.")
NO_SPAN = "(no span)"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def short_name(text):
    """An XLA Ops event is named by its whole HLO instruction,
    ``%fused_adamw.1 = (f32[...]) custom-call(...)``: keep the
    instruction's name, ``fused_adamw.1``."""
    return text.split(" = ", 1)[0].lstrip("%")


def family(name):
    """``ragged_paged_attention.12`` -> ``ragged_paged_attention``: the
    instances of one instruction family (the layers of a kernel, the
    fusions of one kind) summed for the breakdown."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def load(path):
    """{"device": {plane: [event, ...]}, "spans": [event, ...]}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device[plane.name] = [
                        [short_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)]
    spans.sort(key=lambda e: e[1])
    return {"device": device, "spans": spans}


def _union(intervals):
    """Merged, sorted, non-overlapping [start, end] of (start, end)s."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def innermost(spans):
    """The spans flattened to disjoint events, sorted: over each piece
    of the host's time the covering span that started last (of nested
    spans the innermost; of two that start together the one that ends
    first). Time that no span covers is left out."""
    bounds = sorted({x for _, s, d in spans for x in (s, s + d)})
    by_start = sorted(spans, key=lambda e: e[1])
    out, active, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            name, s, d = by_start[i]
            active.append((s, -(s + d), name))
            i += 1
        active = [x for x in active if -x[1] > a]
        if not active:
            continue
        name = max(active)[2]
        if out and out[-1][0] == name and out[-1][1] + out[-1][2] == a:
            out[-1][2] = b - out[-1][1]
        else:
            out.append([name, a, b - a])
    return out


def reduce(events, window_span="bench.trace_window"):
    """Numbers of the traced window. The window is the span
    ``window_span`` (the harness wraps the traced part of its loop in
    it); device events are clipped to it. With several chips, busy and
    per-operation seconds are averaged over them. Each part of each
    idle gap of the device is charged to the INNERMOST host span that
    covers it: a gap inside ``serve.commit`` inside ``serve.step``
    inside ``bench.step`` counts for ``serve.commit`` alone, what only
    ``serve.step`` covers is that span's own time, so the charges add
    up to the idle time. Returns None when the trace holds no device
    plane or no window span."""
    win = [e for e in events["spans"] if e[0] == window_span]
    if not win or not events["device"]:
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    n = len(events["device"])
    busy_ns, ops, counts, gaps_by_span = 0.0, {}, {}, {}
    pieces = innermost([e for e in events["spans"] if e[0] != window_span])
    starts = [p[1] for p in pieces]
    for plane_events in events["device"].values():
        clipped = list(_clip(plane_events, lo, hi))
        merged = _union([(a, b) for _, a, b in clipped])
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in clipped:
            ops[name] = ops.get(name, 0.0) + (b - a)
            counts[name] = counts.get(name, 0) + 1
        # idle gaps: the complement of the union inside the window
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            left = g1 - g0
            first = max(0, bisect.bisect_right(starts, g0) - 1)
            last = bisect.bisect_left(starts, g1)
            for name, a, b in _clip(pieces[first:last], g0, g1):
                gaps_by_span[name] = gaps_by_span.get(name, 0.0) + (b - a)
                left -= b - a
            if left > 0:
                gaps_by_span[NO_SPAN] = gaps_by_span.get(NO_SPAN, 0.0) + left
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns / n / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window_s),
        "op_seconds": {k: v / n / 1e9 for k, v in ops.items()},
        "op_counts": {k: v / n for k, v in counts.items()},
        "idle_seconds_by_span": {k: v / n / 1e9
                                 for k, v in gaps_by_span.items()},
        "chips": n,
    }


def op_seconds(reduced, needle):
    """Device seconds of the operations whose name holds ``needle``."""
    return sum(v for k, v in reduced["op_seconds"].items() if needle in k)


def op_count(reduced, needle):
    """Events of the operations whose name holds ``needle``, a chip."""
    return sum(v for k, v in reduced["op_counts"].items() if needle in k)


def top(mapping, n=10):
    return [[k, v] for k, v in sorted(mapping.items(),
                                      key=lambda kv: -kv[1])[:n]]


def top_families(op_seconds, n=10):
    by = {}
    for name, s in op_seconds.items():
        by[family(name)] = by.get(family(name), 0.0) + s
    return top(by, n)
