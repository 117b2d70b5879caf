"""Share of its roofline that the stage/expert scope reaches in training:
the least time of its useful work (routed-expert FFN on the rows kept
under capacity -- padding is not useful -- plus the dense FFN, forward
and backward, recomputation left out; counts.train_expert_scope) at the
bf16 peak or HBM bandwidth, whichever bounds it, over the measured time
of the scope."""
from __future__ import annotations

from bench import counts

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    t, r = run.trace, run.record
    if t is None or not r["traced_steps"] or r["kept_rows"] is None:
        return None
    s = t.scope_s("stage/expert")
    if s <= 0:
        return None
    w = counts.train_expert_scope(run.cell.cfg, r["tokens_per_step"],
                                  r["kept_rows"])
    least = max(w["flops"] / run.peaks["bf16_flops"],
                w["bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * r["traced_steps"] / s
