"""90th percentile of the admission queue wait over the requests submitted
in the window: first serve/admit mark less the request's arrival (its due
time); a request not admitted by the close counts close - arrival.  A wait
that overlaps the traced part of the window, which the profiler stalls,
is left out.  From the program's span ring (bench/spans.py); numpy's
linear interpolation."""
from __future__ import annotations

import numpy as np

from bench import spans

LAYER = "admission"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def read(run):
    s = spans.spans_between(run, "serve/submit", "serve/admit")
    return float(np.percentile(s, 90)) * 1e3 if s else None
