"""Bytes of latent pool held a live token: ``latent_bytes_held`` (the
count on ``serve.step``: the pool's pages in use at the launch x the
bytes a page really holds over the layers, the rows' lane padding
included; the program states it, so a layout change is read and not
assumed) over ``live_kv_tokens``, the mean over the window's steps that
carry tokens. One unpadded row a token a layer would read
``num_hidden_layers`` x ``mla_costs.row_bytes``; the row's padding and
the part-filled last page of every row come on top."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "B/token"
SOURCE = "program_counter"
MOVES = "serve_tok_s"


def read(run):
    w = program_log.window(run, "serve.step")
    if not w:
        return None
    held = [b / n for b, n in zip(w.counts("latent_bytes_held"),
                                  w.counts("live_kv_tokens")) if n and b]
    return sum(held) / len(held) if held else None
