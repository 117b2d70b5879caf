"""Model FLOP/s utilization of the serving step: forward FLOPs of the tokens
prefilled and decoded in the traced ticks (2 x active parameters plus
attention over each token's keys) over the device's busy seconds times
the bf16 peak."""
from __future__ import annotations

from bench import counts

LAYER = "step"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p95_ms"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    ticks = [k for k in run.record["ticks"] if k["traced"]]
    if t is None or not ticks or t.busy_s <= 0:
        return None
    return 100.0 * served_flops(run.cell.cfg, ticks) \
        / (t.busy_s * run.peaks["bf16_flops"])


def served_flops(cfg, ticks):
    """Forward FLOPs of the tokens prefilled and decoded in the ticks."""
    n = sum(k["prefill_tokens"] + k["decode_tokens"] for k in ticks)
    ctx = sum(k["prefill_ctx"] + k["decode_ctx"] for k in ticks)
    return n * counts.serve_token_flops(cfg, 0) + counts.attn_score_flops(cfg, ctx)
