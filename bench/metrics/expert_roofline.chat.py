"""Share of its roofline that the stage/expert scope reaches in serving:
per tick, each forward pass that runs experts (the prefill chunk, the
decode batch) must read the W8 expert weights once (counts.w8_expert_bytes)
and multiply its routed rows; the least time of that at HBM bandwidth or
the bf16 peak, whichever bounds it, over the measured time of the scope."""
from __future__ import annotations

from bench import counts

LAYER = "kernels"
UNIT = "%"
BETTER = "higher"
MOVES = "tbt_p95_ms"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    ticks = [k for k in run.record["ticks"] if k["traced"]]
    if t is None or not ticks:
        return None
    s = t.scope_s("stage/expert")
    if s <= 0:
        return None
    cfg, pk = run.cell.cfg, run.peaks
    wb = counts.w8_expert_bytes(cfg)
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least = 0.0
    for k in ticks:
        for rows in (k["prefill_tokens"], k["decode_tokens"]):
            if rows:
                f = counts.routed_expert_flops(
                    cfg, rows * cfg["num_experts_per_tok"] * n_moe)
                least += max(f / pk["bf16_flops"], wb / pk["hbm_bytes_per_s"])
    return 100.0 * least / s
