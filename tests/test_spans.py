"""Host spans and request marks (repro.obs.trace): the process-wide span
ring, the serving engine's per-tick spans and request lifecycle marks, and
the device scopes of the paged serving stack and the optimizer update,
which name the HLO and add no op."""
import contextlib
import re
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.recipes import get_recipe
from repro.data.pipeline import DataConfig, make_batch
from repro.models.lm import (ParallelPlan, init_params, paged_decode_step,
                             paged_prefill)
from repro.obs import trace
from repro.obs.sink import MemorySink, Telemetry, null_telemetry
from repro.obs.trace import Span, SpanRing, mark
from repro.obs.report import render
from repro.optim.adamw import AdamWConfig
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.paged_kv import init_paged_cache
from repro.serve.router import ReplicaRouter, RouterConfig
from repro.serve.scheduler import Request
from repro.train.train_step import init_train_state, make_train_step
from tests.conftest import make_mesh11


def _since(seq):
    return trace.RING.records(seq)


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------
def test_span_nesting_parent_and_self_time():
    seq = trace.RING.total
    with Span("outer", k=1) as outer:
        with Span("inner") as a:
            time.sleep(0.002)
        with Span("inner") as b:
            time.sleep(0.002)
        outer.attrs["done"] = True
    recs = _since(seq)
    # children finish (and are appended) before their parent
    assert [r.name for r in recs] == ["inner", "inner", "outer"]
    kids, par = recs[:2], recs[2]
    assert par.parent is None and par.id == outer.id
    assert all(k.parent == outer.id for k in kids)
    assert {a.id, b.id} == {k.id for k in kids}
    assert par.attrs == {"k": 1, "done": True}
    for k in kids:
        assert par.start_ns <= k.start_ns
        assert k.start_ns + k.dur_ns <= par.start_ns + par.dur_ns
    self_ns = par.dur_ns - sum(k.dur_ns for k in kids)
    assert 0 <= self_ns < par.dur_ns
    assert kids[0].dur_ns >= 2e6 and outer.ms >= 4.0
    assert [r.seq for r in recs] == list(range(seq, seq + 3))


def test_span_parent_is_per_thread():
    seq = trace.RING.total
    with Span("main"):
        t = threading.Thread(target=lambda: Span("other").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    recs = {r.name: r for r in _since(seq)}
    assert recs["other"].parent is None


def test_ring_capacity_wrap():
    ring = SpanRing(capacity=4)
    for i in range(10):
        ring.append(f"s{i}", i, 1, i, None, {})
    assert ring.total == 10 and ring.dropped == 6
    assert [r.seq for r in ring.records()] == [6, 7, 8, 9]
    assert [r.name for r in ring.records(8)] == ["s8", "s9"]
    assert [r.seq for r in ring.records(2)] == [6, 7, 8, 9]
    assert ring.records(10) == []


def test_marks_are_instants_under_the_open_span():
    seq = trace.RING.total
    mark("m/outside", rid=3)
    with Span("holder") as sp:
        mark("m/inside", rid=4, at=1.5)
    recs = {r.name: r for r in _since(seq)}
    assert recs["m/outside"].dur_ns is None
    assert recs["m/outside"].parent is None
    assert recs["m/inside"].parent == sp.id
    assert recs["m/inside"].attrs == {"rid": 4, "at": 1.5}


def test_telemetry_flush_writes_each_span_once():
    sink = MemorySink()
    tel = Telemetry(sinks=(sink,))
    with Span("a", x=1):
        mark("b", rid=0)
    tel.flush()
    got = sink.of_kind("span")
    assert [r["name"] for r in got] == ["b", "a"]
    a = got[1]
    assert a["attrs"] == {"x": 1} and a["dur_ns"] > 0
    assert got[0]["parent"] == a["id"] and got[0]["dur_ns"] is None
    tel.flush()                                 # nothing new: nothing again
    assert len(sink.of_kind("span")) == 2
    with Span("c"):
        pass
    tel.flush()
    assert [r["name"] for r in sink.of_kind("span")] == ["b", "a", "c"]
    # spans no longer observe a histogram of their own
    assert not any(m.name == "span_ms" for m in tel.registry)


def test_null_telemetry_flush_writes_no_spans():
    tel = null_telemetry()
    with Span("x"):
        pass
    tel.flush()                                 # NullSink: must not raise


# ---------------------------------------------------------------------------
# the serving engine's spans and marks
# ---------------------------------------------------------------------------
PHASES = ["serve/schedule", "serve/inputs", "serve/dispatch", "serve/wait",
          "serve/emit"]


@pytest.fixture(scope="module")
def tiny_engine():
    cfg = get_arch("qwen15_05b").reduced()
    plan = ParallelPlan(mesh=None)
    params = init_params(cfg, jax.random.key(0))
    ecfg = ServeConfig(max_batch=2, page_size=4, n_pages=32,
                       max_pages_per_req=8, token_budget=64,
                       prefill_buckets=(8, 16), prefill_chunk=8)
    return cfg, plan, params, ecfg


def _drive(eng, reqs):
    """Submit each request when due and tick until all finish; returns
    (results, [the `now` of each tick call])."""
    results, nows = {}, []
    pending = sorted(reqs, key=lambda r: r.arrival_time)
    t0 = time.perf_counter()
    while pending or not eng.sched.idle():
        now = time.perf_counter() - t0
        while pending and pending[0].arrival_time <= now:
            eng.submit(pending.pop(0))
        nows.append(now)
        eng.tick(now, results)
        assert len(nows) < 500
    return results, nows


@pytest.fixture(scope="module")
def driven(tiny_engine):
    """One engine run of four requests: (telemetry, its sink, requests,
    results, the `now` of each tick call, the ring's records of the
    run)."""
    cfg, plan, params, ecfg = tiny_engine
    sink = MemorySink()
    tel = Telemetry(sinks=(sink,))
    eng = ServeEngine(cfg, get_recipe("bf16"), plan, params, ecfg,
                      telemetry=tel)
    r = np.random.default_rng(0)
    reqs = [Request(prompt=list(r.integers(1, cfg.vocab,
                                           int(r.integers(4, 14)))),
                    max_new_tokens=3, arrival_time=0.02 * i, rid=i)
            for i in range(4)]
    seq = trace.RING.total
    results, nows = _drive(eng, reqs)
    return tel, sink, reqs, results, nows, _since(seq)


def test_engine_tick_spans_and_request_marks(driven):
    tel, _, reqs, results, nows, recs = driven
    byid = {x.id: x for x in recs}
    ticks = [x for x in recs if x.name == "serve/tick"]
    # exactly one serve/tick per tick call, whether or not work ran
    assert len(ticks) == len(nows)
    tick_now = {t.id: n for t, n in zip(ticks, nows)}
    for t in ticks:
        kids = [x for x in recs if x.parent == t.id and x.dur_ns is not None]
        names = [k.name for k in kids]
        assert names == (PHASES if t.attrs["ran"] else PHASES[:1])
        for k in kids:
            assert t.start_ns <= k.start_ns
            assert k.start_ns + k.dur_ns <= t.start_ns + t.dur_ns
        assert [k.start_ns for k in kids] == sorted(k.start_ns for k in kids)
        if t.attrs["ran"]:
            assert t.attrs["n_decode"] > 0 or t.attrs["rid"] is not None
    # the step compiled inside some ticks, each counted for --obs-prom
    n_comp = sum(t.attrs["compiled"] for t in ticks)
    assert n_comp >= 2
    assert tel.registry.counter("serve_step_compiles_total").value == n_comp
    # request lifecycle: submit <= admit <= first_token <= finish
    first = {}
    for m in (x for x in recs if x.dur_ns is None):
        t = m.attrs.get("arrival", m.attrs.get("at"))
        first.setdefault(m.attrs["rid"], {}).setdefault(m.name, (t, m))
    assert set(first) == {q.rid for q in reqs}
    for rid, f in first.items():
        ts = [f[n][0] for n in ("serve/submit", "serve/admit",
                                "serve/first_token", "serve/finish")]
        assert ts == sorted(ts), (rid, ts)
        # the first token is stamped at delivery, after its tick began
        ft = f["serve/first_token"][1]
        emit = byid[ft.parent]
        assert emit.name == "serve/emit"
        assert ft.attrs["at"] >= tick_now[emit.parent]
        res = results[rid]
        assert res["first_token"] == ft.attrs["at"]
        assert res["finish"] == f["serve/finish"][0]
        assert res["admit"] == f["serve/admit"][0]


def test_report_renders_the_engine_spans(driven):
    """`python -m repro.obs.report` reads the flushed span records: the
    tick's host/wait split per phase, prefill-chunk vs decode-only ticks
    (the `chunk` attr), recompiled ticks, and per request queue wait,
    prefill and decode (the serve/finish mark)."""
    tel, sink, reqs, _, _, _ = driven
    tel.flush()
    lines = []
    render(list(sink.records), out=lines.append)
    text = "\n".join(lines)
    assert "== serve spans:" in text
    for row in ("host", "inputs", "wait", "emit"):
        assert any(ln.lstrip().startswith(row + " ") for ln in lines), row
    assert "ticks with a prefill chunk:" in text
    assert "ticks decode only:" in text
    assert "prompt tokens per chunk:" in text
    assert "recompiled ticks:" in text
    for row in ("queue wait", "prefill", "decode"):
        got = [ln for ln in lines if ln.lstrip().startswith(row + " ")]
        assert got and f"n={len(reqs):<5d}" in got[-1], (row, got)


def test_router_reads_the_request_clock_at_each_replica(tiny_engine):
    """Replicas tick one after another: each reads the request clock at
    its own tick, after the replica before it has returned, so its
    tokens are stamped at delivery and not early by that replica's
    tick."""
    cfg, plan, params, ecfg = tiny_engine
    engines = [ServeEngine(cfg, get_recipe("bf16"), plan, params, ecfg)
               for _ in range(2)]
    calls = []          # (replica, now, perf_counter at entry, at return)
    routed = {0: [], 1: []}
    for i, e in enumerate(engines):
        def timed(now, results, i=i, tick=e.tick):
            t_in = time.perf_counter()
            ran = tick(now, results)
            calls.append((i, now, t_in, time.perf_counter()))
            return ran

        def submit(req, i=i, sub=e.submit):
            routed[i].append(req.rid)
            sub(req)
        e.tick, e.submit = timed, submit
    reqs = [Request(prompt=[5 + i, 6, 7, 8, 9], max_new_tokens=2,
                    arrival_time=0.0, rid=i) for i in range(2)]
    seq = trace.RING.total
    results = ReplicaRouter(engines, RouterConfig()).run(reqs)
    assert set(results) == {0, 1} and routed == {0: [0], 1: [1]}
    assert [c[0] for c in calls] == [0, 1] * (len(calls) // 2)
    # the router clock's origin, from the first call: its true origin
    # plus the few us between the clock read and the call
    origin = calls[0][2] - calls[0][1]
    pairs = list(zip(calls[0::2], calls[1::2]))
    for a, b in pairs:
        assert b[1] >= a[3] - origin, (a, b)
    # replica 1's first token is stamped after replica 0's tick that
    # ran before it in the same round had ended
    ft = trace.first_marks((m.name, m.attrs) for m in _since(seq)
                           if m.dur_ns is None)[1]["serve/first_token"]
    a, b = next((a, b) for a, b in pairs
                if b[1] <= ft <= b[3] - origin + 1e-3)
    assert ft >= a[3] - origin, (ft, a, b)
    assert results[1]["first_token"] == ft


# ---------------------------------------------------------------------------
# device scopes: in the HLO's op_name metadata, no op added
# ---------------------------------------------------------------------------
def _no_scope(*_a, **_k):
    return contextlib.nullcontext()


def _jaxpr(fn, *args):
    """The jaxpr's text without the addresses of the Python callables it
    holds (custom_vjp rules), which differ between traces."""
    return re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))


def test_paged_step_scopes_attn_and_ffn(tiny_engine, monkeypatch):
    cfg, plan, params, ecfg = tiny_engine
    pools = init_paged_cache(cfg, ecfg.n_pages, ecfg.page_size, fp8_kv=True)
    B, mp = 2, ecfg.max_pages_per_req
    pt = jax.numpy.ones((B, mp), jax.numpy.int32)
    tok = jax.numpy.ones((B, 1), jax.numpy.int32)
    pos = jax.numpy.array([3, 5], jax.numpy.int32)
    act = jax.numpy.array([True, True])
    recipe = get_recipe("bf16")

    def decode(p, pl):
        return paged_decode_step(cfg, recipe, plan, p, pl, pt, tok, pos, act)

    def prefill(p, pl):
        return paged_prefill(cfg, recipe, plan, p, pl, pt[0],
                             jax.numpy.ones((1, 8), jax.numpy.int32),
                             jax.numpy.int32(6))

    for fn in (decode, prefill):
        hlo = jax.jit(fn).lower(params, pools).compile().as_text()
        assert "stage/attn" in hlo and "stage/ffn" in hlo
        scoped = _jaxpr(fn, params, pools)
        with monkeypatch.context() as m:
            m.setattr(trace, "stage_annotation", _no_scope)
            bare = _jaxpr(fn, params, pools)
        assert scoped == bare


@pytest.mark.parametrize("wire", [None, "fp8"])
def test_train_step_scopes_optimizer(monkeypatch, wire):
    from repro.dist import DistPlan
    from repro.train import train_step as ts
    cfg = get_arch("qwen15_05b").reduced()
    mesh = make_mesh11()
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=1e-3)
    dist = DistPlan(wire=wire) if wire else None
    state = init_train_state(cfg, opt, jax.random.key(0), dist=dist)
    batch = make_batch(DataConfig(vocab=cfg.vocab, seq_len=32,
                                  global_batch=2), 0)

    def build():
        return make_train_step(cfg, get_recipe("fp8_flow"), plan, opt,
                               dist=dist, total_steps=10, warmup_steps=2)

    with mesh:
        hlo = jax.jit(build()).lower(state, batch).compile().as_text()
        scoped = _jaxpr(build(), state, batch)
        with monkeypatch.context() as m:
            m.setattr(ts, "stage_annotation", _no_scope)
            bare = _jaxpr(build(), state, batch)
    assert "stage/optimizer" in hlo
    assert scoped == bare


def test_compile_cache_keys_on_scope_metadata(monkeypatch, tmp_path):
    """Programs that differ only in their scopes get cache entries of their
    own, so a program loaded from the cache names its ops as it was
    traced (the benchmark reads stage scopes from the compiled HLO)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch import compile_cache
    flags = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_persistent_cache_min_compile_time_secs": 0,
             "jax_persistent_cache_min_entry_size_bytes": 0,
             "jax_compilation_cache_include_metadata_in_key": False}
    before = {k: getattr(jax.config, k) for k in flags}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def scoped(name):
        def f(x):
            with trace.annotate(name):
                return jax.numpy.sin(x) * 2
        return f

    x = jax.numpy.ones((64,))
    try:
        for k, v in flags.items():
            jax.config.update(k, v)
        cc.reset_cache()
        compile_cache.enable_compile_cache()
        jax.jit(scoped("stage/first")).lower(x).compile()
        jax.clear_caches()                  # the next compile asks the disk
        hlo = jax.jit(scoped("stage/second")).lower(x).compile().as_text()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert "stage/second" in hlo and "stage/first" not in hlo
