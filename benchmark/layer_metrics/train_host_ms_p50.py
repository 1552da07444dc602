"""Median over the window's steps of ``train.step``: the host time inside
``TrainStep.__call__``, entry to return; the device works on past its end
(the program's span log)."""
import statistics

from benchmark import program_log

LAYER = "train step"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "train_tok_s"


def read(run):
    w = program_log.window(run, "train.step")
    return statistics.median(w.step_ms()) if w else None
