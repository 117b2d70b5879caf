"""Device milliseconds per training step in ops under the scope
stage/optimizer: the AdamW update (global norm, clip, moments and the
parameter write) over the whole state."""
from __future__ import annotations

LAYER = "model stages"
UNIT = "ms"
BETTER = "lower"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    t, n = run.trace, run.record["traced_steps"]
    if t is None or not n:
        return None
    s = t.scope_s("stage/optimizer")
    return s / n * 1e3 if s > 0 else None
