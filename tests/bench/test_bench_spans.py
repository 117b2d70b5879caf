"""The readers of the program's spans and scopes: the serving window's
ticks and request marks picked by position in the span ring, and the
device-trace readers of the new scopes, on synthetic runs; then one tiny
chat window on the CPU, whose first-token marks agree with the
benchmark's own token clock."""
import itertools

import numpy as np
import pytest

import bench_tiny
from bench import gen, harness, serve_cell, spans, tracefold
from repro.obs import trace

_ids = itertools.count(10 ** 15)


def read(name, run):
    return harness.reader(harness.ROOT, name).read(run)


def mark(ring, name, parent=None, **attrs):
    ring.append(name, 0, None, next(_ids), parent, attrs)


def tick(ring, host_ms, wait_ms, marks=(), **attrs):
    """One serve/tick span: its marks, a serve/wait child and a serve/emit
    child, then the tick itself (children end first)."""
    tid = next(_ids)
    for name, kw in marks:
        mark(ring, name, parent=tid, **kw)
    ring.append("serve/wait", 0, int(wait_ms * 1e6), next(_ids), tid, {})
    ring.append("serve/emit", 0, int(1e6), next(_ids), tid, {})
    ring.append("serve/tick", 0, int((host_ms + wait_ms) * 1e6), tid, None,
                attrs)


def run_of(traced, attempted, window_s, trace_=None):
    """A run whose i-th tick ran from 0.2 i to 0.2 i + 0.11 s."""
    ticks = [{"traced": t, "t0": 0.2 * i, "t1": 0.2 * i + 0.11}
             for i, t in enumerate(traced)]
    return harness.Run(None, {"ticks": ticks, "attempted": attempted,
                              "window_s": window_s, "traced_steps": 0},
                       trace_, {})


def first_window(ring, traced=(False, False, False), drained=True):
    """Set-up ticks (negative rids), then three requests and three ticks;
    rid 2 is not admitted by the close at 1.0 s.  With ``drained`` the
    third tick finds no request waiting and no prefill to run."""
    tick(ring, 50.0, 0.0, [("serve/admit", {"rid": -1, "at": 0.0})])
    for rid, due in ((0, 0.0), (1, 0.1), (2, 0.2)):
        mark(ring, "serve/submit", rid=rid, arrival=due)
    tick(ring, 3.0, 100.0, [("serve/admit", {"rid": 0, "at": 0.05})])
    tick(ring, 10.0, 100.0, [("serve/first_token", {"rid": 0, "at": 0.3}),
                             ("serve/admit", {"rid": 1, "at": 0.35})])
    last = {"n_waiting": 0, "rid": None} if drained else \
        {"n_waiting": 1, "rid": None}
    tick(ring, 5.0, 100.0, [("serve/first_token", {"rid": 1, "at": 0.5}),
                            ("serve/admit", {"rid": 1, "at": 0.9})], **last)
    return run_of(traced, 3, 1.0)


def second_window(ring):
    tick(ring, 40.0, 0.0)
    mark(ring, "serve/submit", rid=0, arrival=0.0)
    tick(ring, 7.0, 90.0, [("serve/admit", {"rid": 0, "at": 0.01}),
                           ("serve/first_token", {"rid": 0, "at": 0.03})])
    return run_of([False], 1, 0.5)


@pytest.fixture
def ring(monkeypatch):
    r = trace.SpanRing()
    monkeypatch.setattr(trace, "RING", r)
    return r


def test_span_readers_read_only_the_last_window(ring):
    run1 = first_window(ring)
    # host = tick - serve/wait (3, 10, 5 ms), the median
    assert read("tick_host_ms.serve", run1) == pytest.approx(5.0)
    assert read("queue_wait_p90_ms.chat", run1) == pytest.approx(
        float(np.percentile([0.05, 0.25, 0.8], 90)) * 1e3)
    assert read("prefill_p90_ms.chat", run1) == pytest.approx(
        float(np.percentile([0.25, 0.15], 90)) * 1e3)
    run2 = second_window(ring)
    assert read("tick_host_ms.serve", run2) == pytest.approx(7.0)
    assert read("queue_wait_p90_ms.chat", run2) == pytest.approx(10.0)
    assert read("prefill_p90_ms.chat", run2) == pytest.approx(20.0)


def test_traced_part_of_the_window_is_left_out(ring):
    """The second tick is traced: the profiler disturbs the loop from the
    first tick's return (0.11 s) to the start of the third tick (0.4 s),
    which finds nothing waiting and no prefill."""
    run = first_window(ring, traced=(False, True, False))
    ticks = spans.window(run)[0]
    assert spans.traced_part(run, ticks) == pytest.approx((0.11, 0.4))
    assert read("tick_host_ms.serve", run) == pytest.approx(4.0)
    # rid 0 waited 0.0-0.05 s; rid 1 (0.1-0.35) and rid 2 (0.2-close)
    # overlap the stall
    assert read("queue_wait_p90_ms.chat", run) == pytest.approx(50.0)
    # both prefills (0.05-0.3, 0.35-0.5) overlap it: nothing to read
    assert read("prefill_p90_ms.chat", run) is None


def test_traced_part_runs_until_the_backlog_drains(ring):
    """No tick after the traced one finds the queue empty: the disturbed
    part runs to the close; rid 0's wait, over before it, is still read."""
    run = first_window(ring, traced=(False, True, False), drained=False)
    ticks = spans.window(run)[0]
    assert spans.traced_part(run, ticks) == pytest.approx((0.11, 1.0))
    assert read("queue_wait_p90_ms.chat", run) == pytest.approx(50.0)


def test_window_from_the_ring_start_when_nothing_fell_out(ring):
    mark(ring, "serve/submit", rid=0, arrival=0.0)
    tick(ring, 2.0, 10.0, [("serve/admit", {"rid": 0, "at": 0.004})])
    run = run_of([False], 1, 0.5)
    assert read("tick_host_ms.serve", run) == pytest.approx(2.0)
    assert read("queue_wait_p90_ms.chat", run) == pytest.approx(4.0)


SPAN_READERS = ("tick_host_ms.serve", "queue_wait_p90_ms.chat",
                "prefill_p90_ms.chat")


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_give_none_when_the_ring_has_wrapped(monkeypatch, name):
    small = trace.SpanRing(capacity=12)
    monkeypatch.setattr(trace, "RING", small)
    run = first_window(small)
    assert small.dropped > 0
    assert spans.window(run) is None
    assert read(name, run) is None


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_give_none_without_the_ring(monkeypatch, name):
    monkeypatch.delattr(trace, "RING")
    run = run_of([False], 1, 0.5)
    assert read(name, run) is None


def _reduced(scope, durs):
    ops = [tracefold.Op(f"fusion.{i}", scope, float(i), d)
           for i, d in enumerate(durs)]
    ops.append(tracefold.Op("copy.9", "jit(f)/copy", 10.0, 1.0))
    return tracefold.Reduced(20.0, 2.0, ops, [], {})


def test_attn_ms_per_tick_reads_the_paged_attention_scope():
    t = _reduced("jit(engine_step)/while/body/stage/attn/dot_general",
                 [0.01, 0.02])
    assert read("attn_ms_per_tick.serve",
                run_of([True, True, False], 1, 1.0, t)) == \
        pytest.approx(15.0)
    assert read("attn_ms_per_tick.serve", run_of([True], 1, 1.0)) is None
    assert read("attn_ms_per_tick.serve",
                run_of([False], 1, 1.0, t)) is None


def test_optimizer_ms_reads_the_optimizer_scope():
    t = _reduced("jit(train_step)/stage/optimizer/add", [0.03, 0.05])
    run = harness.Run(None, {"traced_steps": 2}, t, {})
    assert read("optimizer_ms.train", run) == pytest.approx(40.0)
    empty = _reduced("jit(train_step)/stage/attn/add", [0.03])
    assert read("optimizer_ms.train",
                harness.Run(None, {"traced_steps": 2}, empty, {})) is None


def test_first_token_marks_match_the_benchmark_clock():
    """A tiny chat window on the CPU: each request's TTFT from its
    serve/submit and serve/first_token marks against the one the
    benchmark's Clock stamps after the tick returns.  The mark is taken
    once the tokens are on the host, before their bookkeeping, on the
    clock the caller passes in, so the Clock is later by the tick's own
    host time after the mark (read from the ring) plus the caller's own
    work around the tick.  That second part is held under 2 ms for every
    request.  The first part is bookkeeping, under 2 ms as a rule; on a
    loaded CPU a host pause (a garbage collection, or another process
    taking the core) can fall into it and make the Clock late, not the
    mark, so it is bounded on its own: the whole gap is under 2 ms at the
    median and for at least three requests in four.  The span readers
    read the window."""
    cell = harness.Cell.resolve("dsv2lite-serve-l5.chat")
    bench_tiny.shrink(cell)
    seed = 2 ** 33 + 7
    eng = serve_cell.build_engine(cell, seed)
    serve_cell.warm(eng, cell.mix, cell.cfg["vocab_size"])
    reqs = gen.requests(cell.mix, cell.cell["rate"], 3.0,
                        cell.cfg["vocab_size"], seed)
    loop = serve_cell.open_loop(eng, reqs, 3.0)
    run = harness.Run(cell, {"ticks": loop["ticks"], "attempted": len(reqs),
                             "window_s": loop["window_s"]}, None, {})
    ticks, kids, marks = spans.window(run)
    first = spans.requests(run, marks)
    emits = {k.id: t for t in ticks for k in kids[t.id]
             if k.name == "serve/emit"}
    ft_mark = {m.attrs["rid"]: m for m in marks
               if m.name == "serve/first_token"}
    times = loop["clock"].times
    gaps = []
    for r in reqs:
        f = first.get(r.index, {})
        if "serve/first_token" not in f or not times.get(r.index):
            continue
        assert f["serve/submit"] == r.due
        gap = (times[r.index][0] - r.due) \
            - (f["serve/first_token"] - f["serve/submit"])
        m = ft_mark[r.index]
        t = emits[m.parent]
        after = (t.start_ns + t.dur_ns - m.start_ns) * 1e-9
        assert after >= 0 and gap >= after - 1e-6, (r.index, gap, after)
        assert gap - after < 2e-3, (r.index, gap, after)
        gaps.append(gap)
    assert len(gaps) >= 3
    assert float(np.median(gaps)) < 2e-3, gaps
    assert sum(g < 2e-3 for g in gaps) >= 0.75 * len(gaps), gaps
    for name in SPAN_READERS:
        assert read(name, run) > 0, name
