"""Model FLOP/s utilization of the training window: tokens per second
times the FLOPs a token REQUIRES (``peaks.train_useful_flops_per_token``:
no recomputation) over chips times the chip's bf16 peak."""
from benchmark import peaks

LAYER = "train step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tok_s"


def read(run):
    if not run.get("peaks") or "train_tok_s" not in run["end_to_end"]:
        return None
    per_token = peaks.train_useful_flops_per_token(run["config"], run["seq"])
    return 100.0 * run["end_to_end"]["train_tok_s"] * per_token \
        / (run["chips"] * run["peaks"]["bf16_flops_per_s"])
