"""Median over the window's engine steps of ``serve.wait``: the host
blocked until the device has finished and the tokens are back, the device
step as the host feels it (the program's span log)."""
from benchmark import program_log

LAYER = "serving step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.phase_p50_ms("serve.wait") if w else None
