"""Share of the device's busy time spent in ``ragged_gated_delta``
events of the trace: the linear layers' recurrence."""
from benchmark import xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_gated_delta"


def read(run):
    t = run.get("trace")
    if not t or not t["busy_s"]:
        return None
    seconds = xplane.op_seconds(t, KERNEL)
    return 100.0 * seconds / t["busy_s"] if seconds else None
