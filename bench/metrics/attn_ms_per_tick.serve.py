"""Device milliseconds per engine tick in ops under the scope stage/attn
(the paged stack's ln1, q/k/v, page write, page read and dequantization,
attention and wo, for prefill chunks and decode)."""
from __future__ import annotations

LAYER = "model stages"
UNIT = "ms"
BETTER = "lower"
MOVES = "tbt_p95_ms"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    n = sum(1 for k in run.record["ticks"] if k["traced"])
    if t is None or not n:
        return None
    s = t.scope_s("stage/attn")
    return s / n * 1e3 if s > 0 else None
