"""Share of the device's busy time in the traced window charged to
the attention projections: q, k, v (their weight transposes, rotary, the
q/k head norms; a latent layer's low-rank q, ``c_kv``, ``k_r`` and the
``w_uk`` absorb) and the ``w_uv`` un-absorb, ``o`` and the residual add
(``benchmark/device_phases.py``: the step executable's instruction ->
phase table joined with the trace's per-instruction seconds)."""
from benchmark import device_phases

LAYER = "serving step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
EXECUTABLE = "serve.step"
PHASES = ("attn.qkv", "attn.out")
PASSES = None


def read(run):
    return device_phases.read(run, EXECUTABLE, PHASES, PASSES)
