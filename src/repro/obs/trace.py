"""Stage-level tracing: device-side named scopes + host-side spans.

Two different clocks, two different tools:

  annotate(name)   TRACE-TIME ONLY — a jax.named_scope over a region of the
                   traced program.  Adds zero ops (it names the HLO, so
                   `jax.profiler` timelines and XLA dumps show the staged
                   layer program's attn/router/dispatch/expert/combine
                   stages, the serving stack's attn/ffn, the optimizer
                   update, the streaming-wire bucket issue points, and the
                   MemoryPlan remat blocks by name).  Safe to leave on
                   unconditionally: tests/test_obs.py asserts the annotated
                   build adds no host-transfer ops.

  Span(name)       HOST wall clock — measures a with-block, records its
                   parent (the span open on this thread when it began) and
                   its attrs, and appends one record to RING when it ends.
                   Each span also brackets its block in a
                   jax.profiler.TraceAnnotation of its own name, so the
                   program's host phases sit on the profiler's host plane,
                   on the same clock as the device ops.

  mark(name, ...)  an instant record in the same ring (request-lifecycle
                   events: submit, admit, first token, finish).

RING is a bounded, process-wide flight recorder, like the profiler's own
buffer: the newest CAPACITY records are kept, older ones fall out.
`Telemetry.flush()` copies the records it has not written yet to its sinks
as `span` records.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import jax

STAGES = ("attn", "router", "dispatch", "expert", "combine")
CAPACITY = 1 << 16


def annotate(name: str):
    """Device-side named scope (zero ops; trace-time metadata only)."""
    return jax.named_scope(name)


def stage_annotation(stage: str):
    """Named scope for one stage of the layer program."""
    return annotate(f"stage/{stage}")


class Record(NamedTuple):
    """One finished span (dur_ns >= 0) or mark (dur_ns None).  `seq` is the
    record's position in the order records were appended; `id`/`parent`
    tie a span to the span that was open when it began."""
    seq: int
    name: str
    start_ns: int                 # time.perf_counter_ns()
    dur_ns: Optional[int]
    id: int
    parent: Optional[int]
    attrs: dict


class SpanRing:
    """Bounded in-memory ring of Records, safe to append from any thread."""

    def __init__(self, capacity: int = CAPACITY):
        self._buf: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.total = 0            # records ever appended

    def append(self, name, start_ns, dur_ns, sid, parent, attrs) -> None:
        with self._lock:
            self._buf.append(Record(self.total, name, start_ns, dur_ns, sid,
                                    parent, attrs))
            self.total += 1

    @property
    def dropped(self) -> int:
        """Records that fell out of the ring."""
        return self.total - len(self._buf)

    def records(self, since: int = 0) -> List[Record]:
        """The kept records with seq >= since, oldest first."""
        with self._lock:
            skip = max(0, since - (self.total - len(self._buf)))
            return list(itertools.islice(self._buf, skip, None))


RING = SpanRing()
_ids = itertools.count()
_open = threading.local()


def _current() -> Optional[int]:
    stack = getattr(_open, "stack", None)
    return stack[-1] if stack else None


def mark(name: str, **attrs) -> None:
    """Append an instant record (its parent is the span open now)."""
    RING.append(name, time.perf_counter_ns(), None, next(_ids), _current(),
                attrs)


def first_marks(marks) -> Dict[int, Dict[str, float]]:
    """{rid: {mark name: its first time}} of request marks given as
    (name, attrs) pairs, oldest first.  A mark's time is its `arrival`
    (serve/submit) or its `at`, both on the caller's request clock."""
    out: Dict[int, Dict[str, float]] = {}
    for name, attrs in marks:
        rid = attrs.get("rid")
        t = attrs.get("arrival", attrs.get("at"))
        if rid is not None and t is not None:
            out.setdefault(rid, {}).setdefault(name, t)
    return out


class Span:
    """Host-side wall-clock span.

    Usage::

        with Span("serve/tick", ran=False) as sp:
            ...work...
            sp.attrs["ran"] = True
        print(sp.ms)
    """

    __slots__ = ("name", "attrs", "id", "parent", "t0", "ms", "_prof")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs
        self.id = self.parent = None
        self.t0 = 0
        self.ms = 0.0
        self._prof = None

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._prof = jax.profiler.TraceAnnotation(self.name)
        self._prof.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self.t0
        self._prof.__exit__(*exc)
        _open.stack.pop()
        self.ms = dur * 1e-6
        RING.append(self.name, self.t0, dur, self.id, self.parent,
                    self.attrs)
        return False
