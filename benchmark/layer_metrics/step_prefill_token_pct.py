"""Share of the query tokens the window's engine steps scheduled that
were prompt tokens of prefill chunks: sum of ``prefill_tokens`` over sum
of ``prefill_tokens`` + ``decode_tokens``, the counts on ``serve.step``.
How much of a step a decoding request waits through is other requests'
prompts."""
from benchmark import program_log

LAYER = "serving host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    if not w:
        return None
    return w.share_pct("prefill_tokens", ("prefill_tokens", "decode_tokens"))
