"""95th percentile of the time between consecutive tokens of a request,
over every such gap delivered in the window."""
from __future__ import annotations

import numpy as np

LAYER = "end_to_end"
UNIT = "ms"
BETTER = "lower"
MOVES = None
SOURCE = "host_clock"


def read(run):
    t = run.record["tbt_s"]
    return float(np.percentile(t, 95)) * 1e3 if t else None
