"""90th percentile of prefill time over the requests admitted in the
window: the serve/first_token mark (stamped at delivery) less the first
serve/admit mark; a request with no first token by the close counts
close - admit.  Chunked prefill (one chunk a tick) and the ticks it shares
with decode; one that overlaps the traced part of the window, which the
profiler stalls, is left out.  From the program's span ring
(bench/spans.py)."""
from __future__ import annotations

import numpy as np

from bench import spans

LAYER = "step"
UNIT = "ms"
BETTER = "lower"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def read(run):
    s = spans.spans_between(run, "serve/admit", "serve/first_token")
    return float(np.percentile(s, 90)) * 1e3 if s else None
