#!/usr/bin/env python3
"""Charge the device's idle time in a kept trace to what the host was
doing, down to the program's own spans.

    python3 benchmark/run.py --workload <cell> --trace 1 --keep-trace <dir>
    python3 benchmark/idle_by_program_span.py <dir or .xplane.pb>

The program's spans (``serve.*``, ``train.*``; ``paddle_tpu/profiler/
spans.py``) are ``jax.profiler.TraceAnnotation``s, so in a trace they sit
on ``/host:CPU`` beside the benchmark's ``bench.*`` ones, on the clock of
the device's ``XLA Ops`` line. The window is ``bench.trace_window``. Each
part of each idle gap of the device is charged to the INNERMOST span that
covers it: a gap inside ``serve.commit`` inside ``serve.step`` inside
``bench.step`` counts for ``serve.commit`` alone, and what only
``serve.step`` covers is that span's own time. (``xplane.reduce`` charges
every covering span, which is right for the flat ``bench.*`` spans the
result line's ``breakdown`` names and double-counts nested ones.)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PREFIXES = ("serve.", "train.", "bench.")
WINDOW = "bench.trace_window"
NO_SPAN = "(no span)"


def load(path):
    """``xplane.load`` keeping the host spans of ``PREFIXES``: its
    ``SPAN_PREFIX`` is the one line a ``benchmark`` issue widens
    (``str.startswith`` takes a tuple as it stands)."""
    from unittest import mock
    from benchmark import xplane
    with mock.patch.object(xplane, "SPAN_PREFIX", PREFIXES):
        return xplane.load(path)


def idle_gaps(plane_events, lo, hi):
    """[(start, end)] inside [lo, hi] in which no event of the plane ran."""
    from benchmark import xplane
    merged = xplane._union([(a, b) for _, a, b in
                            xplane._clip(plane_events, lo, hi)])
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def innermost(spans, g0, g1):
    """{name: ns} of the gap [g0, g1]: each part goes to the covering
    span that started last (of nested spans, the innermost), and to
    ``(no span)`` where none covers it."""
    over = [(s, s + d, name) for name, s, d in spans if s < g1 and s + d > g0]
    cuts = sorted({g0, g1} | {x for a, b, _ in over for x in (a, b)
                              if g0 < x < g1})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        cover = [(s, -e, name) for s, e, name in over if s <= a and e >= b]
        name = max(cover)[2] if cover else NO_SPAN
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def idle_by_span(events, window_span=WINDOW):
    """(window seconds, idle seconds a chip, {span: idle seconds a chip})
    of the traced window, or None without a window or a device plane."""
    win = [e for e in events["spans"] if e[0] == window_span]
    if not win or not events["device"]:
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    spans = [e for e in events["spans"] if e[0] != window_span]
    by, n = {}, len(events["device"])
    for plane_events in events["device"].values():
        for g0, g1 in idle_gaps(plane_events, lo, hi):
            for name, ns in innermost(spans, g0, g1).items():
                by[name] = by.get(name, 0.0) + ns / n / 1e9
    return (hi - lo) / 1e9, sum(by.values()), by


def main(argv):
    from benchmark import xplane
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    events = load(path)
    got = idle_by_span(events)
    if got is None:
        print(f"no {WINDOW} span or no device plane in {path}")
        return 1
    window_s, idle_s, by = got
    steps = sum(1 for e in events["spans"]
                if e[0] in ("serve.step", "train.step"))
    print(f"window {window_s:.4f} s, device idle {idle_s:.4f} s "
          f"({100 * idle_s / window_s:.2f} %), {steps} program steps")
    print(f"{'innermost span':<24}{'idle s':>10}{'share %':>9}"
          f"{'ms a step':>11}")
    for name, s in sorted(by.items(), key=lambda kv: -kv[1]):
        per = f"{1e3 * s / steps:>11.3f}" if steps else f"{'':>11}"
        print(f"{name:<24}{s:>10.4f}{100 * s / idle_s:>9.1f}{per}")
    # what the program's spans leave unnamed: under the benchmark's span
    # around the call into the program and nothing deeper, or no span
    alone = by.get("bench.step", 0.0) + by.get(NO_SPAN, 0.0)
    print(f"on bench.step alone or {NO_SPAN}: {100 * alone / idle_s:.1f} % "
          f"of the idle time")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
