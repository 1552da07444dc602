"""Share of the device's busy time in the traced window charged to
instructions no phase owns: the instrument's own coverage
(``benchmark/device_phases.py``: the step executable's instruction ->
phase table joined with the trace's per-instruction seconds)."""
from benchmark import device_phases

LAYER = "serving step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
EXECUTABLE = "serve.step"
PHASES = (device_phases.UNSCOPED,)
PASSES = None


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
