"""Share of the device's busy time spent in ``ragged_latent_attention``
events of the trace."""
from benchmark import xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "itl_p95_ms"
KERNEL = "ragged_latent_attention"


def read(run):
    t = run.get("trace")
    if not t or not t["busy_s"]:
        return None
    seconds = xplane.op_seconds(t, KERNEL)
    return 100.0 * seconds / t["busy_s"] if seconds else None
