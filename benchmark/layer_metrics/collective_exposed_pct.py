"""Share of the traced window that ONE chip spends in collectives with
nothing else running beside them: on the first device's line, the union
of the all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute events (a ``-start`` / ``-done`` pair counts through
its own two events: the transfer between them, under which compute runs,
is no event), less what any other operation's event covers, over the
window. An event of ``while`` / ``conditional`` / ``call`` spans its
body's events, the collectives among them, so it covers nothing here.

On one chip the trace holds no collective and the reader returns None.
"""
from benchmark import xplane

LAYER = "sharding"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "async-collective")
#: events that span their body's events
CONTAINERS = ("while", "conditional", "call")
WINDOW_SPAN = "bench.trace_window"


def exposed_ns(line_events, lo, hi):
    """(exposed, collective) nanoseconds of one device line in [lo, hi]:
    the collectives' union, and the part of it no other event covers."""
    coll, other = [], []
    for name, a, b in xplane._clip(line_events, lo, hi):
        if any(c in name for c in COLLECTIVES):
            coll.append((a, b))
        elif xplane.family(name) not in CONTAINERS:
            other.append((a, b))
    coll, other = xplane._union(coll), xplane._union(other)
    total = sum(b - a for a, b in coll)
    covered, j = 0.0, 0
    for a, b in coll:
        while j < len(other) and other[j][1] <= a:
            j += 1
        k = j
        while k < len(other) and other[k][0] < b:
            covered += min(b, other[k][1]) - max(a, other[k][0])
            k += 1
    return total - covered, total


def read(run):
    events = run.get("events")
    if not events or not events.get("device"):
        return None
    win = [e for e in events["spans"] if e[0] == WINDOW_SPAN]
    if not win:
        return None
    lo, hi = win[0][1], win[0][1] + win[0][2]
    line = events["device"][sorted(events["device"])[0]]
    exposed, total = exposed_ns(line, lo, hi)
    if not total:
        return None
    return 100.0 * exposed / (hi - lo)
