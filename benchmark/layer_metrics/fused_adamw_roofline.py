"""The fused AdamW kernel's share of its roofline. It is bound by HBM:
``peaks.adamw_bytes`` (p, g, m, v read; p, m, v written, f32) over the
chip's bandwidth is the least time an update can take; divided by the
device time of one update's ``fused_adamw`` events in the trace."""
from benchmark import peaks, xplane

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"
KERNEL = "fused_adamw"


def read(run):
    if not run.get("trace") or not run.get("peaks"):
        return None
    seconds = xplane.op_seconds(run["trace"], KERNEL)
    updates = xplane.op_count(run["trace"], KERNEL)
    if not seconds or not updates:
        return None
    floor = peaks.adamw_bytes(run["n_params"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor / (seconds / updates)
