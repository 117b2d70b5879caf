"""A serving cell: the continuous-batching engine (``serve/engine.py``) under
open-loop traffic at the cell's fixed rate.

Set-up makes the bf16 weights on the device in one jitted call from the
seed, builds the engine as ``launch/serve.run`` does (W8 experts, FP8 KV
pages), drops the bf16 tree, and runs every engine-step shape the mix can
produce through ``ServeEngine.tick``.  The window then submits each request when it is due and
calls ``ServeEngine.tick`` whenever there is work.  Every token is stamped
with the host time at which the tick that made it returned; TTFT runs from
the request's due time, so a stall delays every request behind it.  A
request with no first token when the window closes counts as close - due.

Correctness: after the window, a sample of the finished requests drawn from
the seed, with the one that served most tokens in it, goes through the
plain reference (float32 forward over prompt + served tokens), and the
widest gap by which a served token lies below the reference's best logit
is held to the cell's limit; a program compiled inside the window makes
the run not correct.
"""
from __future__ import annotations

import gc
import math
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, gen, weights
from bench.harness import compiles, log, memory_peak_bytes, reference

SAMPLE_TOKENS = 300         # served tokens the check covers, at least
SAMPLE_MAX_REQUESTS = 8
SEQ_BLOCK = 1024            # reference sequences pad to a multiple of this


def engine_config(cell, seed: int):
    from repro.serve.engine import ServeConfig
    e, sv = cell.mix["engine"], cell.cfg["serving"]
    ps = sv["page_size"]
    pages = -(-e["max_len"] // ps)
    return ServeConfig(
        max_batch=e["max_batch"], page_size=ps,
        n_pages=e["max_batch"] * pages + 1, max_pages_per_req=pages,
        token_budget=e["max_batch"] * e["max_len"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        prefill_chunk=e["prefill_chunk"], fp8_kv=sv["fp8_kv"],
        w8_weights=sv["w8_experts"], prefix_cache=e["prefix_cache"],
        seed=seed)


def build_engine(cell, seed: int):
    from repro.configs.base import ArchConfig
    from repro.core.recipes import get_recipe
    from repro.launch import sharding
    from repro.launch.mesh import make_device_mesh
    from repro.serve.engine import ServeEngine

    cfg = cell.cfg
    arch = ArchConfig(**cfg["arch"])
    mesh = make_device_mesh()
    plan = sharding.make_plan(arch, mesh)
    make = lambda k: weights.make_tree(cfg, k)  # noqa: E731
    key = weights.seed_key(seed)
    sh = sharding.tree_specs(arch, mesh, jax.eval_shape(make, key))
    params = jax.jit(make, out_shardings=sh)(key)
    engine = ServeEngine(arch, get_recipe(cfg["recipe"]), plan, params,
                         engine_config(cell, seed))
    del params                  # the engine holds its W8 tree
    return engine


def warm_requests(mix, vocab: int):
    """Prompts that make every prefill shape of the mix: for each (bucket,
    history) of ``gen.engine_shapes``, a prompt whose first chunk (no
    history) or second chunk (history) lands in that bucket."""
    chunk = mix["engine"]["prefill_chunk"]
    lens = [chunk + min(b, chunk) if h else min(b, chunk)
            for b, h in gen.engine_shapes(mix)]
    return [[1 + (i % (vocab - 1)) for i in range(n)] for n in lens]


def warm(engine, mix, vocab: int):
    """Run every engine-step shape the mix can produce through
    ``ServeEngine.tick`` itself: first each prompt alone with one token to
    make (prefill only, no decode), then a request that decodes while each
    prompt is prefilled again, until it decodes alone.  Every shape runs
    again after the first step, so each is ready for the pool as the steps
    hand it on.  Returns the argument shapes of each step shape, for
    naming the ops of a trace (``hlo_texts``)."""
    from repro.serve.scheduler import Request

    prompts = warm_requests(mix, vocab)
    seen = {}
    step = engine._step_fn

    def recording(*args, **static):
        seen[(static["bucket"], static["any_decode"], static["history"])] = \
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                np.shape(a), a.dtype, sharding=getattr(a, "sharding", None)),
                args)
        return step(*args, **static)

    def drain():
        while engine.sched.waiting or engine.sched.active:
            engine.tick(0.0, {})

    engine._step_fn = recording
    try:
        rid = -1
        for p in prompts:
            engine.submit(Request(prompt=p, max_new_tokens=1, rid=rid))
            rid -= 1
            drain()
        chunks = sum(-(-len(p) // mix["engine"]["prefill_chunk"])
                     for p in prompts)
        engine.submit(Request(prompt=prompts[0], max_new_tokens=chunks + 3,
                              rid=rid))
        engine.tick(0.0, {})
        for p in prompts:
            rid -= 1
            engine.submit(Request(prompt=p, max_new_tokens=1, rid=rid))
        drain()
    finally:
        engine._step_fn = step
    return seen


def hlo_texts(engine, shapes):
    """The compiled HLO of every step shape (from the compile cache), for
    naming the ops of a trace; ``shapes`` as ``warm`` returns them."""
    out = []
    with engine.plan.mesh:
        for (bucket, dec, history), args in sorted(
                shapes.items(), key=lambda kv: str(kv[0])):
            out.append(engine._step_fn.lower(
                *args, bucket=bucket, any_decode=dec,
                history=history).compile().as_text())
    return out


class Clock:
    """Token stamps per request, from what the engine holds after a tick."""

    def __init__(self):
        self.times = {}          # rid -> [host time of each token]
        self.finished = set()

    def stamp(self, rid, n, t):
        ts = self.times.setdefault(rid, [])
        if n > len(ts):
            ts.extend([t] * (n - len(ts)))

    def after_tick(self, engine, results, t):
        for st in engine.sched.active.values():
            self.stamp(st.req.rid, len(st.generated), t)
        for rid in results.keys() - self.finished:
            self.stamp(rid, len(results[rid]["tokens"]), t)
            self.finished.add(rid)


def open_loop(engine, reqs, seconds, trace_dir=None):
    """Drive the engine for ``seconds`` from now; returns the window's
    record.  With ``trace_dir`` a part of the window is traced."""
    from bench import tracefold
    from repro.serve.scheduler import Request

    results = {}
    clock = Clock()
    ticks, lateness = [], []
    t_from, t_len = 0.3 * seconds, min(5.0, 0.4 * seconds)
    tracing = done = False
    win = None
    sched = engine.sched
    nxt = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        rel = now - t0
        if rel >= seconds:
            break
        if trace_dir and not tracing and not done and rel >= t_from:
            jax.profiler.start_trace(trace_dir)
            win = jax.profiler.TraceAnnotation(tracefold.WINDOW_SPAN)
            win.__enter__()
            tracing, t_tr = True, time.perf_counter()
        if tracing and time.perf_counter() - t_tr >= t_len:
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
            tracing, done = False, True
        with jax.profiler.TraceAnnotation("bench/submit"):
            while nxt < len(reqs) and reqs[nxt].due <= rel:
                r = reqs[nxt]
                engine.submit(Request(prompt=r.prompt, max_new_tokens=r.max_new,
                                      arrival_time=r.due, rid=r.index))
                lateness.append(rel - r.due)
                nxt += 1
        if sched.waiting or sched.active:
            before = {st.req.rid: (st.prefill_pos, len(st.generated))
                      for st in sched.active.values()}
            with jax.profiler.TraceAnnotation("bench/tick"):
                engine.tick(rel, results)
            t_ret = time.perf_counter()
            clock.after_tick(engine, results, t_ret - t0)
            ticks.append(_tick_record(engine, results, before, rel,
                                      t_ret - t0, tracing))
        else:
            wait = reqs[nxt].due - rel if nxt < len(reqs) else seconds - rel
            with jax.profiler.TraceAnnotation("bench/idle"):
                time.sleep(max(0.0, min(wait, seconds - rel)))
    t_end = time.perf_counter() - t0
    if tracing:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {"results": results, "clock": clock, "ticks": ticks,
            "lateness": lateness, "submitted": nxt, "window_s": t_end}


def _tick_record(engine, results, before, t_a, t_b, traced):
    """What one tick did: prompt tokens prefilled (with the keys each
    attended to) and tokens decoded (with their positions)."""
    pf_tok = pf_ctx = dec_tok = dec_ctx = 0
    states = {st.req.rid: st for st in engine.sched.active.values()}
    for rid, st in states.items():
        p0, g0 = before.get(rid, (0, 0))
        c = st.prefill_pos - p0
        if c > 0:
            pf_tok += c
            pf_ctx += c * p0 + c * (c + 1) // 2
        if len(st.generated) > g0 and g0 > 0:
            dec_tok += 1
            dec_ctx += len(st.req.prompt) + g0
    for rid, (p0, g0) in before.items():
        if rid not in states and rid in results and g0 > 0:
            dec_tok += 1                    # finished by this tick's decode
            dec_ctx += p0 + g0
    return {"t0": t_a, "t1": t_b, "prefill_tokens": pf_tok,
            "prefill_ctx": pf_ctx, "decode_tokens": dec_tok,
            "decode_ctx": dec_ctx, "traced": traced}


def latencies(reqs, loop):
    """TTFT of every request due in the window and every gap between
    consecutive tokens delivered in it (seconds)."""
    end = loop["window_s"]
    times = loop["clock"].times
    ttft, tbt = [], []
    for r in reqs:
        ts = [t for t in times.get(r.index, []) if t <= end]
        ttft.append((ts[0] if ts else end) - r.due)
        tbt.extend(np.diff(ts).tolist())
    return ttft, tbt


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------
def sample(reqs, loop, seed: int):
    """Finished requests for the check: the one that served most tokens,
    then others drawn from the seed until SAMPLE_TOKENS are covered."""
    res = loop["results"]
    done = [r for r in reqs if r.index in res]
    if not done:
        return []
    done.sort(key=lambda r: (-len(res[r.index]["tokens"]), r.index))
    rng = np.random.default_rng([seed, 9])
    rest = [done[i] for i in rng.permutation(len(done) - 1) + 1]
    out, n = [done[0]], len(res[done[0].index]["tokens"])
    for r in rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(r)
        n += len(res[r.index]["tokens"])
    return out


def reference_gaps(cell, seed, items, quants=()):
    """items: [(prompt, served tokens)].  Returns the gap of every served
    token, and for each lower-precision or faulty reference in ``quants``
    (``deepseek_v2.QUANTS``) the gaps of the tokens it would put first."""
    cfg = cell.cfg
    ref = reference(cell.root, cfg)
    k, E = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    key = weights.seed_key(seed)
    params = jax.jit(lambda kk: weights.make_tree(cfg, kk, padded=False))(key)
    fns = {}

    def fn(S, cap, q):
        if (S, cap, q) not in fns:
            fns[(S, cap, q)] = jax.jit(
                lambda p, t, pos, sv, j: ref.served_gaps(
                    cfg, p, t, pos, sv, capacity=cap, quant=q, judge=j))
        return fns[(S, cap, q)]

    gaps, others = [], {q: [] for q in quants}
    with jax.default_matmul_precision("highest"):
        for prompt, served in items:
            seq = list(prompt) + list(served)
            S = -(-len(seq) // SEQ_BLOCK) * SEQ_BLOCK
            toks = np.zeros((1, S), np.int32)
            toks[0, :len(seq)] = seq
            pos = np.arange(len(prompt) - 1, len(seq) - 1, dtype=np.int32)
            sv = np.asarray(served, np.int32)
            cap = -(-3 * S * k // E // 128) * 128
            while True:                  # dropless: widen until none drop
                g, at, dropped = fn(S, cap, None)(params, toks, pos, sv, None)
                if int(dropped) == 0:
                    break
                cap *= 2
            gaps.append(np.asarray(g))
            for q in quants:
                cg, _, _ = fn(S, cap, q)(params, toks, pos, sv, at)
                others[q].append(np.asarray(cg))
    return gaps, others


# ---------------------------------------------------------------------------
def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        fault: Optional[str] = None) -> dict:
    from bench import tracefold

    mix, cfg = cell.mix, cell.cfg
    marks = [("start", time.perf_counter() - t_start)]
    engine = build_engine(cell, seed)
    marks.append(("engine built", time.perf_counter() - t_start))
    if fault == "alter_token":
        _alter_tokens(engine, cfg["vocab_size"])
    elif fault == "stale_pool":
        _forget_pages(engine)
    shapes = warm(engine, mix, cfg["vocab_size"])
    reqs = gen.requests(mix, cell.cell["rate"], seconds, cfg["vocab_size"],
                        seed)
    setup_s = time.perf_counter() - t_start
    marks.append(("warm", setup_s))
    log("[serve] set-up " + ", ".join(f"{n} at {t:.1f}s" for n, t in marks))
    c0 = compiles()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    loop = open_loop(engine, reqs, seconds, trace_dir)
    window_compiles = compiles()["n"] - c0["n"]
    peak = memory_peak_bytes()
    hlo = hlo_texts(engine, shapes) if trace else None
    engine = None
    gc.collect()

    reduced = None
    if trace:
        ops, spans = tracefold.read_xplane(tracefold.find_xplane(trace_dir),
                                           hlo)
        reduced = tracefold.reduce(ops, spans)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)

    ttft, tbt = latencies(reqs, loop)
    res = loop["results"]
    picked = sample(reqs, loop, seed)
    items = [(r.prompt, res[r.index]["tokens"]) for r in picked]
    gaps, _ = reference_gaps(cell, seed, items)
    gap = max((float(np.max(g)) for g in gaps), default=math.inf)
    correct, compared = check.judge(
        {"gap": gap, "window_compiles": window_compiles},
        check.limits(cell))
    lat = np.asarray(loop["lateness"]) if loop["lateness"] else np.zeros(1)
    n_tok = sum(len(g) for g in gaps)
    log(f"[serve] {len(reqs)} requests due, {loop['submitted']} submitted, "
        f"{len(res)} finished, {len(loop['ticks'])} ticks in "
        f"{loop['window_s']:.3f}s; set-up {setup_s:.1f}s ({len(shapes)} "
        f"step shapes); compiles in window {window_compiles}; TTFT ms p50 "
        f"{np.percentile(ttft, 50) * 1e3:.3f} p75 "
        f"{np.percentile(ttft, 75) * 1e3:.3f} p90 "
        f"{np.percentile(ttft, 90) * 1e3:.3f}; generator late "
        f"p50 {np.median(lat) * 1e3:.3f} ms max {lat.max() * 1e3:.3f} ms; "
        f"peak {peak} B; checked {len(items)} requests, {n_tok} served tokens")
    return {"kind": "serve", "setup_s": setup_s, "window_s": loop["window_s"],
            "ttft_s": ttft, "tbt_s": tbt, "ticks": loop["ticks"],
            "compile_s": c0["s"], "window_compiles": window_compiles,
            "memory_peak_bytes": peak, "attempted": len(reqs), "failed": 0,
            "correct": correct, "compared": compared, "reduced": reduced,
            "checked_tokens": n_tok}


def _alter_tokens(engine, vocab):
    """Fault for the harness test: every request's third token is served as
    the next id over (and fed back as such)."""
    emit = engine._emit

    def altered(st, tok, now, results):
        if len(st.generated) == 2:
            tok = (tok + 1) % vocab
        emit(st, tok, now, results)
    engine._emit = altered


def _forget_pages(engine):
    """Fault for the harness test: the KV pool comes back from every tick
    with every page blank, as if nothing had been written."""
    tick = engine.tick
    blank = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    donate_argnums=0)

    def forgetful(now, results):
        ran = tick(now, results)
        engine.pools = blank(engine.pools)
        return ran
    engine.tick = forgetful
