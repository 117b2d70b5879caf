"""Training tokens per second: every token of the steps completed in the
window over the window's length.  Each step ends in the blocking fetch of
its metrics."""
from __future__ import annotations

LAYER = "end_to_end"
UNIT = "tokens/s"
BETTER = "higher"
MOVES = None
SOURCE = "host_clock"


def read(run):
    r = run.record
    return r["tokens"] / r["window_s"] if r["steps"] else None
