"""Median host time of one train step inside the window: from the call
of ``TrainStep`` to its loss read back on the host."""
import statistics

LAYER = "train step"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "train_tok_s"


def read(run):
    s = run.get("step_s")
    return 1e3 * statistics.median(s) if s else None
