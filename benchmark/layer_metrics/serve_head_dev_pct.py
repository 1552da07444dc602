"""Share of the device's busy time in the traced window charged to
the step's epilogue: the final norm and the logits, the is-finite guard,
and the sampler (``speculative_sample`` with all of ``if_any_samples``)
(``benchmark/device_phases.py``: the step executable's instruction ->
phase table joined with the trace's per-instruction seconds)."""
from benchmark import device_phases

LAYER = "serving step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
EXECUTABLE = "serve.step"
PHASES = ("head", "guard", "sample")
PASSES = None


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
