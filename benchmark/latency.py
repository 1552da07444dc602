"""Arithmetic from token stamps to the serving metrics. Pure Python over
plain numbers so it can be checked on a hand-made event list."""
import math


def percentile(values, q):
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def ttft_samples(due, stamps, t_open, t_close):
    """One sample per request DUE inside [t_open, t_close): first token
    time minus due time; a request with no first token by ``t_close``
    enters with its age so far. Returns (samples, censored count).

    ``due``: {rid: due time}; ``stamps``: {rid: [token times]}, all on
    one clock."""
    out, censored = [], 0
    for rid, d in due.items():
        if not t_open <= d < t_close:
            continue
        ts = stamps.get(rid)
        if not ts or ts[0] > t_close:
            out.append(t_close - d)
            censored += 1
        else:
            out.append(ts[0] - d)
    return out, censored


def gap_samples(stamps, t_open, t_close):
    """Gaps between successive tokens of one request, for every token
    emitted inside the window that has a predecessor (which may lie
    before the window), pooled over requests."""
    out = []
    for ts in stamps.values():
        for a, b in zip(ts, ts[1:]):
            if t_open <= b < t_close:
                out.append(b - a)
    return out


def tokens_in(stamps, t_open, t_close):
    return sum(1 for ts in stamps.values() for t in ts
               if t_open <= t < t_close)


def step_stats(step_s):
    """What the steps of a window looked like, for an info line: a
    stalled host shows as a long tail here (``stalled_s`` is the time
    beyond twice the median, summed over steps)."""
    if not step_s:
        return {}
    p50 = percentile(step_s, 50)
    return {"step_ms_p50": 1e3 * p50,
            "step_ms_p99": 1e3 * percentile(step_s, 99),
            "step_ms_max": 1e3 * max(step_s),
            "stalled_s": sum(x - 2 * p50 for x in step_s if x > 2 * p50)}
