"""Share of the device's busy time in the traced window charged to the
linear layers' mixer: in-projections, convolution with the slot's last
inputs, L2 norms and gates (``linear.proj``); the recurrence kernel with
its operands' layout and the slots' gather and scatter
(``linear.scan``); the gated norm, ``out_proj`` and the residual add
(``linear.out``) (``benchmark/device_phases.py``). A model without
linear layers has nothing to read."""
from benchmark import device_phases

LAYER = "serving step"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
EXECUTABLE = "serve.step"
PHASES = ("linear.proj", "linear.scan", "linear.out")
PASSES = None


def read(run):
    charged = device_phases.charges(run, EXECUTABLE)
    if not charged or not any(phase in PHASES for phase, _ in charged):
        return None
    return device_phases.share(charged, run["trace"]["busy_s"], PHASES,
                               PASSES)
