"""Query tokens the ragged paged-attention kernel serves a walk over KV:
mean (``prefill_tokens`` + ``decode_tokens``) over mean
``attn_kv_walks``, the counts on ``serve.step``, over the window's
engine steps. A decode row is one walk for one token; a prefill chunk
walked a q block of 8 at a time reads at most 8, walked a q tile at a
time as many as the tile holds. A program without the count reads
nothing."""
import statistics

from benchmark import program_log

LAYER = "kernels"
UNIT = "tokens"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    if not w:
        return None
    walks = statistics.fmean(w.counts("attn_kv_walks"))
    tokens = statistics.fmean(a + b for a, b in zip(
        w.counts("prefill_tokens"), w.counts("decode_tokens")))
    # a program from before the count, or a window with nothing aboard
    return tokens / walks if walks and tokens else None
