"""Share of the device's busy time spent in ``grouped_matmul`` events of
the trace: the routed experts' three grouped matrix products a sparse
layer (``paddle_tpu/kernels/grouped_matmul.py``)."""
from benchmark import xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "grouped_matmul"


def read(run):
    t = run.get("trace")
    seconds = t and t["busy_s"] and xplane.op_seconds(t, KERNEL)
    return 100.0 * seconds / t["busy_s"] if seconds else None
