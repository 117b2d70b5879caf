"""Model FLOP/s utilization of the training step: useful FLOPs of the
traced steps (6 x active parameters per token plus causal attention
scores; recomputation does not count) over the traced window times the
chip's bf16 peak."""
from __future__ import annotations

from bench import counts

LAYER = "step"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    t, r = run.trace, run.record
    if t is None or not r["traced_steps"]:
        return None
    flops = counts.train_flops_per_token(run.cell.cfg, run.cell.mix["seq_len"]) \
        * r["tokens_per_step"] * r["traced_steps"]
    return 100.0 * flops / (t.window_s * run.peaks["bf16_flops"])
