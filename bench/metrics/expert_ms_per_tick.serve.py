"""Device milliseconds per engine tick in ops under the scope stage/expert
(the routed experts of prefill chunks and decode; the serving path runs
the dense FFN unscoped)."""
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
    s = t.scope_s("stage/expert")
    return s / n * 1e3 if s > 0 else None
