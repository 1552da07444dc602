"""Mean over the window's engine steps of the rows in the plan over the
row slots: ``rows`` / ``max_num_seqs``, the counts on ``serve.step``."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.mean_ratio_pct("rows", "max_num_seqs") if w else None
