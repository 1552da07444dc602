"""Median over the window's engine steps of the host time in
``serve.assemble``: the numpy build of the launch's operands (the
program's span log)."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.phase_p50_ms("serve.assemble") if w else None
