"""Share of the device's busy time in the traced window charged to
the optimizer: the flat staging round the kernel, ``copy`` included, and
``fused_adamw`` itself (``benchmark/device_phases.py``: the step
executable's instruction -> phase table joined with the trace's per-
instruction seconds)."""
from benchmark import device_phases

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"
EXECUTABLE = "train.step"
PHASES = ("optimizer",)
PASSES = None


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
