"""90th percentile of time to first token over every request due in the
window: from the request's due time to the return of the tick that made
its first token; a request with none by the close counts close - due.
Percentiles are numpy's linear interpolation."""
from __future__ import annotations

import numpy as np

LAYER = "end_to_end"
UNIT = "ms"
BETTER = "lower"
MOVES = None
SOURCE = "host_clock"


def read(run):
    t = run.record["ttft_s"]
    return float(np.percentile(t, 90)) * 1e3 if t else None
