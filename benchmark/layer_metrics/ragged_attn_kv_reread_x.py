"""How many times the ragged paged-attention kernel's walk covers a
live KV token: mean ``attn_kv_tokens_read`` over mean ``live_kv_tokens``,
the counts on ``serve.step``, over the window's engine steps. The
roofline's floor (``ragged_attn_roofline``) reads every live token once;
the kernel walks a chunk's prefix once a q block and rounds every block's
horizon up to a slab, so this is what stands between that kernel and its
floor once the walk itself is cheap. 1 is the least."""
import statistics

from benchmark import program_log

LAYER = "kernels"
UNIT = "x"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    if not w:
        return None
    covered = statistics.fmean(w.counts("attn_kv_tokens_read"))
    live = statistics.fmean(w.counts("live_kv_tokens"))
    # a program from before the count, or a window with nothing aboard
    return covered / live if covered and live else None
