"""95th percentile of the time in ``serve.queue``, ``add_request`` to
admission, over the requests enqueued inside the window; one still
waiting at its end enters with its age (the program's span log)."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "ttft_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.request_p95("queue_ms") if w else None
