"""Reduction of a profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed HLO
op (a ``while`` loop and the ops of its body alike), named by the op's HLO
text, ``%fusion.12 = (bf16[..]{..}) fusion(...), kind=kLoop, ...``, with
its time in ``device_offset_ps``/``device_duration_ps`` and no scope.  The
scope path (the ``jax.named_scope`` stack, with autodiff and remat wrappers
such as ``transpose(jvp(...))`` and ``checkpoint``) is the ``op_name`` in
the metadata of that instruction in the compiled program's HLO text, which
the cell's runner hands over: an op is matched by its name and result type.
Host threads are lines of ``/host:CPU``; the benchmark's own spans there are
``jax.profiler.TraceAnnotation`` events named ``bench/...``, and the one
named ``bench/window`` brackets the traced window.  Device and host events
share the profiler's clock.

What it computes (each a plain function of event lists, so tests can feed
synthetic ones):
  busy        union of op intervals inside the window (overlaps count once)
  scope time  summed durations of ops whose scope path holds a scope name
  idle gaps   the holes in the union, each labelled by the host span that
              overlaps it most (what the host was doing while the chip idled)
  breakdown   the ops that took most time and the longest labelled gaps
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/window"


@dataclasses.dataclass(frozen=True)
class Op:
    name: str          # HLO op name (e.g. "fusion.12")
    scope: str         # scope path ("jit(step)/.../stage/expert/dot_general")
    start: float       # seconds
    dur: float         # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except Exception:       # a stat the binding cannot convert
        return {}


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")
_OPNAME = re.compile(r', metadata=\{[^}]*?op_name="([^"]*)"')


def head(instr: str) -> str:
    """"%name = <result type>" of an HLO instruction's text: the key that
    ties a trace event to its line in the program's HLO."""
    name, _, rest = instr.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                return f"{name} = {rest[:i + 1]}"
    return f"{name} = {rest.split(' ', 1)[0]}"


def scope_map(hlo_texts: Sequence[str]) -> Dict[str, str]:
    """{head: op_name} over every instruction of the given HLO modules; a
    head that two modules give different scopes is dropped."""
    out: Dict[str, str] = {}
    clash = set()
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            n = _OPNAME.search(line)
            key, scope = head(m.group(1)), n.group(1) if n else ""
            if out.get(key, scope) != scope:
                clash.add(key)
            out[key] = scope
    for k in clash:
        del out[k]
    return out


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {files}")
    return files[0]


def read_xplane(path: str, hlo_texts: Sequence[str] = ()):
    """Returns ({device id: [Op]}, [Span] of host events named bench/*);
    each op's scope comes from ``hlo_texts`` (empty where not found)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = scope_map(hlo_texts)
    ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(2))
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                out = ops.setdefault(dev, [])
                for ev in line.events:
                    h = head(ev.name)
                    out.append(Op(h.split(" = ")[0].lstrip("%"),
                                  scopes.get(h, ""), ev.start_ns * 1e-9,
                                  ev.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                          ev.duration_ns * 1e-9))
    return ops, spans


def describe(path: str, per_line: int = 6) -> str:
    """Planes, lines and the first events of each with their stats: what a
    reader needs to see before trusting the names above."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                out.append(f"    {ev.name!r} start {ev.start_ns} dur "
                           f"{ev.duration_ns} {_stats(ev)}")
    return "\n".join(out)


def window_of(spans: Sequence[Span]) -> Tuple[float, float]:
    w = [s for s in spans if s.name == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0].start, w[0].end


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------
def clip(ops: Sequence[Op], t0: float, t1: float) -> List[Op]:
    """Ops cut to the window [t0, t1]; ops wholly outside are dropped."""
    out = []
    for o in ops:
        s, e = max(o.start, t0), min(o.end, t1)
        if e > s:
            out.append(dataclasses.replace(o, start=s, dur=e - s))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(ops: Sequence[Op], t0: float, t1: float) -> float:
    return sum(e - s for s, e in union([(o.start, o.end)
                                         for o in clip(ops, t0, t1)]))


def _unwrap(part: str) -> str:
    """"transpose(jvp(stage))" -> "stage": the transformation wrappers JAX
    puts around a name-stack component."""
    while True:
        m = re.match(r"^\w+\((.*)\)$", part)
        if not m:
            return part
        part = m.group(1)


def has_scope(op: Op, scope: str) -> bool:
    """True when ``scope`` ("stage/expert") is a run of whole components of
    the op's scope path, whatever wraps it (jvp, transpose, checkpoint)."""
    parts = [_unwrap(p) for p in op.scope.split("/")]
    want = scope.split("/")
    n = len(want)
    return any(parts[i:i + n] == want for i in range(len(parts) - n + 1))


def scope_seconds(ops: Sequence[Op], scope: str, t0: float, t1: float) -> float:
    """Seconds in which an op under ``scope`` ran: the union of their
    intervals, so a loop and the ops of its body count once."""
    return sum(e - s for s, e in union([(o.start, o.end)
                                         for o in clip(ops, t0, t1)
                                         if has_scope(o, scope)]))


def leaves(ops: Sequence[Op]) -> List[Op]:
    """The ops that hold no other op (a while loop holds its body's)."""
    order = sorted(ops, key=lambda o: (o.start, -o.dur))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, o in enumerate(order):
        while stack and order[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= order[stack[-1]].end:
            parent[stack[-1]] = True
        stack.append(i)
    return [o for o, p in zip(order, parent) if not p]


def idle_gaps(ops: Sequence[Op], spans: Sequence[Span], t0: float,
              t1: float) -> List[Tuple[str, float]]:
    """Every hole in the busy union inside [t0, t1], labelled by the host
    span (other than the window itself) that overlaps it most, or
    "no host span"; longest first."""
    busy = union([(o.start, o.end) for o in clip(ops, t0, t1)])
    holes, cur = [], t0
    for s, e in busy:
        if s > cur:
            holes.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        holes.append((cur, t1))
    host = [s for s in spans if s.name != WINDOW_SPAN]
    out = []
    for hs, he in holes:
        best, label = 0.0, "no host span"
        for sp in host:
            ov = min(he, sp.end) - max(hs, sp.start)
            if ov > best:
                best, label = ov, sp.name
        out.append((label, he - hs))
    return sorted(out, key=lambda g: -g[1])


def op_label(op: Op) -> str:
    """An op's name for the breakdown: its innermost stage scope, if any,
    and its HLO name without the numeric suffix."""
    stage = re.findall(r"stage/(\w+)", op.scope)
    base = re.sub(r"[.\d]+$", "", op.name) or op.name
    return f"{stage[-1]}:{base}" if stage else base


def breakdown(ops: Sequence[Op], gaps: Sequence[Tuple[str, float]],
              t0: float, t1: float, n: int = 10) -> dict:
    tot: Dict[str, float] = defaultdict(float)
    for o in leaves(clip(ops, t0, t1)):
        tot[op_label(o)] += o.dur
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in list(gaps)[:n]]}


@dataclasses.dataclass
class Reduced:
    """What the per-layer metric readers see of one traced window."""
    window_s: float
    busy_s: float                      # mean over devices
    ops: List[Op]                      # device 0, clipped to the window
    gaps: List[Tuple[str, float]]      # device 0
    breakdown: dict

    def scope_s(self, scope: str) -> float:
        return scope_seconds(self.ops, scope, -math.inf, math.inf)


def reduce(ops_by_dev: Dict[int, List[Op]], spans: Sequence[Span],
           devices: Optional[Sequence[int]] = None) -> Reduced:
    t0, t1 = window_of(spans)
    devs = sorted(ops_by_dev) if devices is None else list(devices)
    if not devs:
        raise ValueError("the trace holds no device ops")
    busy = [busy_seconds(ops_by_dev.get(d, []), t0, t1) for d in devs]
    ops0 = clip(ops_by_dev.get(devs[0], []), t0, t1)
    gaps = idle_gaps(ops0, spans, t0, t1)
    return Reduced(t1 - t0, sum(busy) / len(busy), ops0, gaps,
                   breakdown(ops0, gaps, t0, t1))
