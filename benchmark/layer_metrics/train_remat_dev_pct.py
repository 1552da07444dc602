"""Share of the device's busy time in the traced window charged to
recomputed forwards, every phase: what memory ``full`` costs against
``dots_saveable``. It cuts ACROSS ``train_attn_dev_pct``,
``train_mlp_dev_pct`` and ``train_loss_dev_pct`` (their remat pass is in
them too) (``benchmark/device_phases.py``: the step executable's
instruction -> phase table joined with the trace's per-instruction
seconds)."""
from benchmark import device_phases

LAYER = "train step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"
EXECUTABLE = "train.step"
PHASES = None
PASSES = ("remat",)


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
