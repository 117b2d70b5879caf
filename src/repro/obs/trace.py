"""Stage-level tracing: device-side named scopes + host-side spans.

Two different clocks, two different tools:

  annotate(name)   TRACE-TIME ONLY — a jax.named_scope over a region of the
                   traced program.  Adds zero ops (it names the HLO, so
                   `jax.profiler` timelines and XLA dumps show the staged
                   layer program's attn/router/dispatch/expert/combine
                   stages, the streaming-wire bucket issue points, and the
                   MemoryPlan remat blocks by name).  Safe to leave on
                   unconditionally: tests/test_obs.py asserts the annotated
                   build adds no host-transfer ops.

  Span(tel, name)  HOST wall-clock — measures a with-block in ms, feeds the
                   telemetry's 'span' histogram, and (when the profiler is
                   active) brackets the block in a jax.profiler
                   TraceAnnotation so host phases line up with device rows
                   on the trace viewer.  This is how train/loop.py splits
                   the formerly-conflated `dt` into an honest device-step
                   span and the blocking host-fetch span.
"""
from __future__ import annotations

import time

import jax

STAGES = ("attn", "router", "dispatch", "expert", "combine")


def annotate(name: str):
    """Device-side named scope (zero ops; trace-time metadata only)."""
    return jax.named_scope(name)


def stage_annotation(stage: str):
    """Named scope for one stage of the staged layer program."""
    return annotate(f"stage/{stage}")


class Span:
    """Host-side wall-clock span; records into telemetry on exit.

    Usage::

        with tel.span("device_step") as sp:
            ...work...
        print(sp.ms)
    """

    __slots__ = ("tel", "name", "t0", "ms", "_prof")

    def __init__(self, tel, name: str):
        self.tel, self.name = tel, name
        self.t0 = 0.0
        self.ms = 0.0
        self._prof = None

    def __enter__(self):
        self._prof = jax.profiler.TraceAnnotation(f"host/{self.name}")
        self._prof.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (time.perf_counter() - self.t0) * 1e3
        self._prof.__exit__(*exc)
        if self.tel is not None:
            self.tel.histogram("span_ms", labels={"span": self.name}) \
                .observe(self.ms)
        return False
