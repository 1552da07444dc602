"""Share of the device's busy time spent in ``ragged_paged_attention``
events of the trace."""
from benchmark import xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_paged_attention"


def read(run):
    t = run.get("trace")
    if not t or not t["busy_s"]:
        return None
    return 100.0 * xplane.op_seconds(t, KERNEL) / t["busy_s"]
