"""95th percentile of the time in ``serve.prefill``, admission to the
first generated token, over the requests enqueued inside the window; one
still prefilling at its end enters with its age, one still waiting with 0
(the program's span log). Per-layer metrics are read in traced runs, and
the profiler's stop holds the engine for over a second once inside the
window: a request prefilling just then is that much longer, so set this
against the same run's ``ttft_p95_ms`` or another traced run's, not
against an untraced run's."""
from benchmark import program_log

LAYER = "serving step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.request_p95("prefill_ms") if w else None
