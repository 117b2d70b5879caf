"""The serving engine's own spans and request marks of one window, read
from the program's process-wide span ring (``repro.obs.trace.RING``).

Records are picked by position in the ring, so two runs in one process
each read their own window: the window's ticks are the last
``len(run.record["ticks"])`` ``serve/tick`` spans (set-up ticks come
before them, and nothing ticks after the window), and its request marks
are those appended after the ``serve/tick`` span just before the first of
them.  Window requests are rids ``0 .. attempted-1`` (set-up uses negative
rids).  Mark times are on the request clock, the one ``window_s`` and the
requests' due times are on.

A program without the ring, or a ring that no longer holds the whole
window and the tick before it, gives None: the metric is left out.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

TICK = "serve/tick"


def window(run) -> Optional[Tuple[list, Dict[int, list], list]]:
    """(tick spans in order, {tick id: its child spans}, marks) of the
    run's window, or None."""
    try:
        from repro.obs import trace
    except ImportError:
        return None
    ring = getattr(trace, "RING", None)
    n = len(run.record["ticks"])
    if ring is None or not n:
        return None
    recs = ring.records()
    at = [i for i, r in enumerate(recs) if r.name == TICK]
    if len(at) < n:
        return None
    if len(at) > n:
        lo = at[-n - 1] + 1
    elif ring.dropped == 0:
        lo = 0
    else:
        return None
    ticks = [recs[i] for i in at[-n:]]
    ids = {t.id for t in ticks}
    kids: Dict[int, list] = {i: [] for i in ids}
    marks = []
    for r in recs[lo:]:
        if r.dur_ns is None:
            marks.append(r)
        elif r.parent in ids:
            kids[r.parent].append(r)
    return ticks, kids, marks


def requests(run, marks) -> Dict[int, Dict[str, float]]:
    """{rid: {mark name: first request-clock time}} for the window's
    requests; ``serve/submit`` gives the arrival."""
    from repro.obs import trace
    n = run.record["attempted"]
    first = trace.first_marks((m.name, m.attrs) for m in marks)
    return {rid: f for rid, f in first.items()
            if isinstance(rid, int) and 0 <= rid < n}


def traced_part(run, ticks) -> Optional[Tuple[float, float]]:
    """The part of the window the profiler disturbs, on the request clock:
    from the return of the last tick before the first traced one, through
    the stall of stopping the profiler (it writes the trace: seconds), to
    the start of the first later tick that finds no request waiting and
    no prefill to run, once the backlog the stall left has drained.
    ``ticks`` are the window's serve/tick spans (their attrs)."""
    rec = run.record["ticks"]
    at = [i for i, k in enumerate(rec) if k["traced"]]
    if not at:
        return None
    lo = rec[at[0] - 1]["t1"] if at[0] > 0 else 0.0
    hi = run.record["window_s"]
    for i in range(at[-1] + 1, len(rec)):
        a = ticks[i].attrs
        if a.get("n_waiting") == 0 and a.get("rid") is None:
            hi = rec[i]["t0"]
            break
    return lo, hi


def spans_between(run, start: str, end: str) -> Optional[List[float]]:
    """Seconds from each window request's first ``start`` mark to its
    first ``end`` mark; a request with no ``end`` by the close counts
    ``window_s`` - start.  A request whose interval overlaps the traced
    part of the window is left out: the profiler's stall, and the backlog
    it leaves, are no part of the engine's queue or prefill.  None where
    the ring cannot give the window."""
    w = window(run)
    if w is None:
        return None
    close = run.record["window_s"]
    skip = traced_part(run, w[0])
    out = []
    for f in requests(run, w[2]).values():
        if start not in f:
            continue
        a, b = f[start], min(f.get(end, close), close)
        if skip is None or b <= skip[0] or a >= skip[1]:
            out.append(b - a)
    return out
