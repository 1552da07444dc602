"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals / window)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_tok_s"


def read(run):
    t = run.get("trace")
    return t["idle_pct"] if t else None
