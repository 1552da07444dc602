"""Share of the device's busy time in the traced window charged to
a routed layer's glue round its three grouped products: the router
matmul, sigmoid, group limit and top-k; ``dispatch_plan``'s argsort and
the gather into expert rows; the gate-weighted sum back and the routed
counts (``benchmark/device_phases.py``: the step executable's
instruction -> phase table joined with the trace's per-instruction
seconds)."""
from benchmark import device_phases

LAYER = "serving step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
EXECUTABLE = "serve.step"
PHASES = ("moe.route", "moe.dispatch", "moe.combine")
PASSES = None


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
