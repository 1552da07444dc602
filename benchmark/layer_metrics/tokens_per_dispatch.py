"""Output tokens a host dispatch yields: how full the engine's steps are.

Delta of the program's ``tokens_generated`` over delta of its
``host_dispatches`` across the window (``metrics_snapshot()``)."""
LAYER = "serving host"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(run):
    c = run.get("counters")
    if not c or not c.get("host_dispatches"):
        return None
    return c["tokens_generated"] / c["host_dispatches"]
