"""Median over the window's engine steps of the host time in ``serve.plan``:
tick, shed and abort, admission with the prefix probe, and the
scheduler's planning of the launch (the program's span log)."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.phase_p50_ms("serve.plan") if w else None
