"""Median over the window's engine steps of the host time after the
tokens are back: ``serve.commit`` (the per-row loop with ``stream_cb`` and
finalization) plus the self time of ``serve.step`` (metrics and the flight
recorder), from the program's span log."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.phase_p50_ms("serve.commit", with_self=True) if w else None
