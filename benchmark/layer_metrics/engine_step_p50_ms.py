"""Median host time of one ``LLMEngine.step()`` inside the window: the
benchmark's clock around the call, which ends in the token readback."""
import statistics

LAYER = "serving step"
UNIT = "ms"
SOURCE = "host_clock"
MOVES = "itl_p95_ms"


def read(run):
    s = run.get("step_s")
    return 1e3 * statistics.median(s) if s else None
