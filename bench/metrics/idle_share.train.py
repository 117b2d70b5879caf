"""Share of the traced window in which no op ran on the device: 1 - union
of the XLA op intervals / window."""
from __future__ import annotations

LAYER = "device and host loop"
UNIT = "%"
BETTER = "lower"
MOVES = "train_tokens_per_s"
SOURCE = "device_trace"


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
