"""Bytes of KV pool held a live token: the pages in use in both page
groups x their bytes (``used_pages`` over the full-attention layers,
``window_pages_used`` over the window layers; counts on ``serve.step``,
page bytes from the configuration, ``benchmark/moe_costs.py``) over
``live_kv_tokens``, the mean over the window's steps that carry tokens.
Every layer holding every token would read ``num_hidden_layers`` x one
layer's bytes a token; window layers that release the pages behind the
window read towards the full layers' bytes alone as contexts grow."""
from benchmark import moe_costs, program_log

LAYER = "serving host"
UNIT = "B/token"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(run):
    w = program_log.window(run, "serve.step")
    if not w or "layer_types" not in run["config"]:
        return None
    full, window = moe_costs.page_bytes(run["config"])
    held = [(a * full + b * window) / n
            for a, b, n in zip(w.counts("used_pages"),
                               w.counts("window_pages_used"),
                               w.counts("live_kv_tokens")) if n]
    # a program from before the window group counts no window pages
    return sum(held) / len(held) \
        if held and any(w.counts("window_pages_used")) else None
