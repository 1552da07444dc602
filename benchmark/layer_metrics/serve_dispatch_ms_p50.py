"""Median over the window's engine steps of the host time in
``serve.dispatch``: the host-to-device puts and the call of the ragged
executable, until it returns (the program's span log)."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.phase_p50_ms("serve.dispatch") if w else None
