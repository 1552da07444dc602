"""Mean over the window's engine steps of the KV pool's pages in use
over the pages it has: ``used_pages`` / ``num_pages``, the counts on
``serve.step``. Memory reserved against memory in use."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(run):
    w = program_log.window(run, "serve.step")
    return w.mean_ratio_pct("used_pages", "num_pages") if w else None
