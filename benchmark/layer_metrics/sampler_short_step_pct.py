"""Share of the window's launched engine steps in which no row aboard
sampled: ``sampled_rows`` == 0, the count on ``serve.step`` (live rows
with ``temperature`` > 0, from ``_launch``'s operands). Such a step's
sampling epilogue is an argmax; any other runs the sampler for every
row, and its top-k / nucleus sorts where ``masked_rows`` > 0. 100 says
that the cell pays for the short branch alone and measures no other. A
step that launched nothing carries no count and is left out."""
from benchmark import program_log

LAYER = "serving step"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "itl_p95_ms"


def read(run):
    w = program_log.window(run, "serve.step")
    if not w:
        return None
    sampled = [s[5]["sampled_rows"] for s in w.steps
               if "sampled_rows" in (s[5] or {})]
    if not sampled:             # a program from before the count
        return None
    return 100.0 * sum(n == 0 for n in sampled) / len(sampled)
