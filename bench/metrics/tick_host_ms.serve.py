"""Host milliseconds of an engine tick: the median, over the window's
untraced ticks, of the serve/tick span less its serve/wait child (the host
blocked on the chip's result), read from the program's span ring
(bench/spans.py).  Scheduling, the step's inputs, dispatch and token
bookkeeping: the part of every token gap the chip waits for."""
from __future__ import annotations

import numpy as np

from bench import spans

LAYER = "device and host loop"
UNIT = "ms"
BETTER = "lower"
MOVES = "tbt_p95_ms"
SOURCE = "program_span"


def read(run):
    w = spans.window(run)
    if w is None:
        return None
    ticks, kids, _ = w
    host = [t.dur_ns - sum(k.dur_ns for k in kids[t.id]
                           if k.name == "serve/wait")
            for t, rec in zip(ticks, run.record["ticks"]) if not rec["traced"]]
    return float(np.median(host)) * 1e-6 if host else None
