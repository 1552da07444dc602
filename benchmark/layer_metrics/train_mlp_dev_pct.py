"""Share of the device's busy time in the traced window charged to
the SwiGLU feed-forward, forward, backward and recomputed
(``benchmark/device_phases.py``: the step executable's instruction ->
phase table joined with the trace's per-instruction seconds)."""
from benchmark import device_phases

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"
EXECUTABLE = "train.step"
PHASES = ("mlp",)
PASSES = None


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
