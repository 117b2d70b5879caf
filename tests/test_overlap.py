"""Overlapped EP dispatch pipeline + chunked prefill scheduling.

Covers the PR-2 tentpole: moe_block_overlapped forward/grad parity vs the
synchronous moe_block (fp8_flow / naive_fp8 / bf16), the unchanged Fig.-2
cast count (2 for fp8_flow at any n_chunks), the fused single-message
dispatch (2 collectives per chunk vs 5 for the synchronous block), the real
moe_block_decode drop fraction, chunked-prefill parity and scheduler
invariants (FCFS preserved; decode never starved more than one chunk), and
the unified serve_step sampling."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.core import casts
from repro.core.moe import (DispatchPlan, MoEConfig, moe_block,
                            moe_block_decode, moe_block_decode_overlapped,
                            moe_block_overlapped)
from repro.core.recipes import get_recipe
from tests.conftest import make_mesh11


# ---------------------------------------------------------------------------
# moe_block_overlapped parity vs the synchronous block.
# ---------------------------------------------------------------------------
def _toy_moe(seed=1, T=256, D=256, F=128, E=4, topk=2, cf=4.0):
    """capacity_factor is ample so neither block drops (capacities are
    per-chunk in the overlapped block, so drop SETS could differ under
    overflow — parity is defined on the no-drop regime)."""
    cfg = MoEConfig(n_experts=E, top_k=topk, d_model=D, d_ff=F,
                    capacity_factor=cf)
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(T, D)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    wr = jnp.asarray(r.normal(size=(D, E)).astype(np.float32) * 0.02)
    w13 = jnp.asarray(r.normal(size=(E, D, 2 * F)).astype(np.float32) * 0.05)
    w2 = jnp.asarray(r.normal(size=(E, F, D)).astype(np.float32) * 0.05)
    return cfg, (x, wr, w13, w2)


def _sharded_block(recipe, cfg, mesh, block, jit=True, **kw):
    def body(x, wr, w13, w2):
        y, m = block(recipe, cfg, x, wr, w13, w2, **kw)
        # the worst shard's drop fraction: invariant, so it fits out_spec P()
        drop = jax.lax.stop_gradient(m["drop_frac"])
        return y, jax.lax.pmax(drop, ("data", "model"))

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(("data", "model"), None), P(None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(("data", "model"), None), P()))
    return jax.jit(sm) if jit else sm


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-30)


# naive_fp8's Wgrad layouts are rebuilt by dequantize->transpose->requantize
# with COLUMN tiles spanning the capacity dim, so chunking changes its
# quantization groups — the double-quantization error the paper identifies is
# genuinely chunk-sensitive; the casting-free recipes are not.
GRAD_RTOL = {"bf16": 1e-3, "fp8_flow": 2e-2, "naive_fp8": 1.5e-1}


@pytest.mark.parametrize("name", ["fp8_flow", "bf16", "naive_fp8"])
def test_overlap_forward_and_grad_parity(name):
    recipe = get_recipe(name)
    mesh = make_mesh11()
    cfg, args = _toy_moe()
    f_sync = _sharded_block(recipe, cfg, mesh, moe_block)
    f_ovl = _sharded_block(recipe, cfg, mesh, moe_block_overlapped,
                           n_chunks=2)
    y0, d0 = f_sync(*args)
    y1, d1 = f_ovl(*args)
    assert float(d0) == 0.0 and float(d1) == 0.0
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32), atol=2e-2)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) ** 2)

    g0 = jax.grad(loss(f_sync), argnums=(0, 2, 3))(*args)
    g1 = jax.grad(loss(f_ovl), argnums=(0, 2, 3))(*args)
    for a, b in zip(g0, g1):
        assert _rel_err(a, b) < GRAD_RTOL[name], (name, _rel_err(a, b))


def test_overlap_multidevice_parity(n_chunks=2):
    """Real 2-rank EP: dispatch/combine actually cross ranks.  (Deeper
    pipelines are exercised on the 1x1 mesh above — this compile is the
    expensive one, so one multi-device depth keeps CI within budget.)"""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    recipe = get_recipe("fp8_flow")
    mesh = make_mesh((1, 2), ("data", "model"))
    cfg, args = _toy_moe(T=256)
    f_sync = _sharded_block(recipe, cfg, mesh, moe_block)
    f_ovl = _sharded_block(recipe, cfg, mesh, moe_block_overlapped,
                           n_chunks=n_chunks)
    y0, d0 = f_sync(*args)
    y1, d1 = f_ovl(*args)
    assert float(d0) == 0.0 and float(d1) == 0.0
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32), atol=2e-2)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32) ** 2)

    g0 = jax.grad(loss(f_sync), argnums=(0, 2, 3))(*args)
    g1 = jax.grad(loss(f_ovl), argnums=(0, 2, 3))(*args)
    for a, b in zip(g0, g1):
        assert _rel_err(a, b) < 2e-2


@pytest.mark.parametrize("n_chunks", [2, 4])
def test_overlap_cast_count_stays_two(n_chunks):
    """Chunk boundaries never re-quantize: ONE entry quantize over the whole
    block and ONE hoisted backward island quantize — the Fig.-2 count is 2
    at any pipeline depth, and no explicit dequantize ever materializes."""
    recipe = get_recipe("fp8_flow")
    mesh = make_mesh11()
    cfg, args = _toy_moe()
    # traced once, not jitted: jit's differentiation would trace the
    # forward twice and the ledger counts trace-time casts
    f_ovl = _sharded_block(recipe, cfg, mesh, moe_block_overlapped,
                           jit=False, n_chunks=n_chunks)
    with casts.ledger() as led:
        jax.make_jaxpr(jax.grad(
            lambda *a: jnp.sum(f_ovl(*a)[0].astype(jnp.float32) ** 2),
            argnums=(0, 2, 3)))(*args)
    assert led.activation_casts() == 2, led.summary()
    assert not [e for e in led.events if e.kind == "dequantize"]


def test_overlap_fuses_dispatch_into_one_collective():
    """The synchronous block launches 5 forward all-to-alls (payload, scale,
    expert ids, probs, combine); the overlapped block packs payload+scales+
    metadata into ONE uint8 message per chunk: 2 per chunk total."""
    recipe = get_recipe("fp8_flow")
    mesh = make_mesh11()
    cfg, args = _toy_moe()

    def count_a2a(fn):
        return str(jax.make_jaxpr(fn)(*args)).count("all_to_all")

    assert count_a2a(_sharded_block(recipe, cfg, mesh, moe_block)) == 5
    for n in (1, 2, 4):
        f = _sharded_block(recipe, cfg, mesh, moe_block_overlapped,
                           n_chunks=n)
        assert count_a2a(f) == 2 * n


def test_dispatch_plan_chunking():
    assert DispatchPlan(n_chunks=4, min_chunk_tokens=64).chunks_for(256) == 4
    assert DispatchPlan(n_chunks=4, min_chunk_tokens=64).chunks_for(128) == 2
    assert DispatchPlan(n_chunks=4, min_chunk_tokens=64).chunks_for(63) == 1
    # clamps to a divisor of T
    assert DispatchPlan(n_chunks=3, min_chunk_tokens=1).chunks_for(256) == 2


# ---------------------------------------------------------------------------
# moe_block_decode: real drop fraction.
# ---------------------------------------------------------------------------
def test_moe_decode_reports_real_drop_frac():
    """All tokens route to expert 0 (uniform router => top_k tie-break picks
    index 0), overflowing C_dec: drop_frac must report the real dropped
    fraction, not 0.0."""
    recipe = get_recipe("bf16")
    mesh = make_mesh11()
    T, D, E = 64, 128, 4
    cfg = MoEConfig(n_experts=E, top_k=1, d_model=D, d_ff=128)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(T, D)),
                    jnp.bfloat16)
    wr = jnp.zeros((D, E), jnp.float32)
    w13 = jnp.ones((E, D, 256), jnp.bfloat16) * 0.01
    w2 = jnp.ones((E, 128, D), jnp.bfloat16) * 0.01

    def body(x, wr, w13, w2):
        y, m = moe_block_decode(recipe, cfg, x, wr, w13, w2)
        return m["drop_frac"]

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(None, None), P(None, None),
                                 P("model", None, None), P("model", None, None)),
                       out_specs=P())
    # C_dec = round_up(2*64*1/4, 8) = 32 slots for expert 0; 64 assignments
    assert float(sm(x, wr, w13, w2)) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# moe_block_decode_overlapped: the prefetched (chunk-pipelined psum) decode
# path must match the synchronous psum path bitwise in the no-drop regime.
# ---------------------------------------------------------------------------
def _sharded_decode(recipe, cfg, mesh, block, **kw):
    """Decode-style sharding: tokens REPLICATED across the EP axis, experts
    sharded — the combine is a psum, not an all-to-all."""
    def body(x, wr, w13, w2):
        y, m = block(recipe, cfg, x, wr, w13, w2, **kw)
        # aux is rank-identical (full-batch router on replicated x); the
        # pmean proves the invariance to the replication checker and is
        # bitwise-neutral (sum of P equal po2-divisible terms)
        return y, jax.lax.pmean(m["aux_loss"], "model"), m["drop_frac"]

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(None, None), P(), P())))


@pytest.mark.parametrize("name", ["fp8_flow", "bf16"])
@pytest.mark.parametrize("n_chunks", [2, 4])
def test_decode_overlap_parity(name, n_chunks):
    """Chunking the decode batch is exact: decode tokens never interact
    below the combine, the router runs over the WHOLE batch (aux identical
    at any depth), and the fp8 entry quantize happens once (row scales are
    row-local).  Bitwise parity vs the synchronous psum."""
    recipe = get_recipe(name)
    mesh = make_mesh11()
    cfg, args = _toy_moe(T=64, cf=4.0)
    y0, a0, d0 = _sharded_decode(recipe, cfg, mesh, moe_block_decode)(*args)
    y1, a1, d1 = _sharded_decode(recipe, cfg, mesh,
                                 moe_block_decode_overlapped,
                                 n_chunks=n_chunks)(*args)
    assert float(d0) == 0.0 and float(d1) == 0.0
    # per-token math is identical; the per-chunk C_dec changes the grouped
    # GEMM's padded shape, and XLA's shape-dependent tiling can wobble the
    # bf16 output by 1 ulp — tolerance pinned to that, far below fp8 error
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32), atol=1e-2)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))


def test_decode_overlap_parity_multidevice():
    """Real 2-rank EP: the combine psums actually cross ranks."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    recipe = get_recipe("fp8_flow")
    mesh = make_mesh((1, 2), ("data", "model"))
    cfg, args = _toy_moe(T=64, cf=4.0)
    y0, a0, d0 = _sharded_decode(recipe, cfg, mesh, moe_block_decode)(*args)
    y1, a1, d1 = _sharded_decode(recipe, cfg, mesh,
                                 moe_block_decode_overlapped,
                                 n_chunks=2)(*args)
    assert float(d0) == 0.0 and float(d1) == 0.0
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32), atol=1e-2)


def test_decode_overlap_pipelines_the_psum():
    """Depth n converts the single combine psum into an n-deep chain (+1
    for the drop-fraction scalar), with each chunk's dispatch/expert
    compute traced BETWEEN consecutive combine psums — the double-buffer
    window XLA's latency-hiding scheduler needs."""
    recipe = get_recipe("fp8_flow")
    mesh = make_mesh11()
    cfg, args = _toy_moe(T=64, cf=4.0)

    def jaxpr_of(block, **kw):
        return str(jax.make_jaxpr(
            lambda *a: _sharded_decode(recipe, cfg, mesh, block, **kw)(*a))(
            *args))

    jx_sync = jaxpr_of(moe_block_decode)
    # combine + drop_frac (+1: the harness's aux pmean lowers to a psum)
    assert jx_sync.count("psum") == 3
    for n in (2, 4):
        jx = jaxpr_of(moe_block_decode_overlapped, n_chunks=n)
        assert jx.count("psum") == n + 2, (n, jx.count("psum"))
        # grouped-FFN GEMMs appear between the first and last combine psum
        first, last = jx.find("psum"), jx.rfind("psum")
        assert jx.find("dot_general", first, last) != -1

    # tiny decode batches degrade to the synchronous depth
    assert DispatchPlan().decode_chunks_for(4) == 1
    assert DispatchPlan().decode_chunks_for(64) == 2
    assert DispatchPlan(decode_chunks=4).decode_chunks_for(64) == 4


# ---------------------------------------------------------------------------
# Chunked prefill: model-level parity + engine/scheduler invariants.
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dense_setup():
    from repro.configs import get_arch
    from repro.models.lm import ParallelPlan, init_params
    cfg = get_arch("qwen15_05b").reduced()
    mesh = make_mesh11()
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    params = init_params(cfg, jax.random.key(0))
    return cfg, mesh, plan, params


def _cos(a, b):
    a = np.asarray(a, np.float32).ravel()
    b = np.asarray(b, np.float32).ravel()
    return a @ b / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-30)


def test_chunked_prefill_matches_monolithic(dense_setup):
    from repro.models.lm import paged_prefill
    from repro.serve.paged_kv import PageAllocator, init_paged_cache
    cfg, mesh, plan, params = dense_setup
    recipe = get_recipe("bf16")
    ps, mp = 8, 8
    prompt = list(np.random.default_rng(1).integers(1, cfg.vocab, 22))
    alloc = PageAllocator(32, ps)
    pages = alloc.alloc(alloc.pages_for(len(prompt)))
    ptrow = np.zeros((mp,), np.int32)
    ptrow[:len(pages)] = pages

    pools = init_paged_cache(cfg, 32, ps, fp8_kv=False)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :len(prompt)] = prompt
    with mesh:
        lg_m, _ = paged_prefill(cfg, recipe, plan, params, pools,
                                jnp.asarray(ptrow), jnp.asarray(toks),
                                jnp.int32(len(prompt)))

    pools2 = init_paged_cache(cfg, 32, ps, fp8_kv=False)
    t1 = np.zeros((1, 16), np.int32)
    t1[0, :] = prompt[:16]
    t2 = np.zeros((1, 16), np.int32)
    t2[0, :len(prompt) - 16] = prompt[16:]
    with mesh:
        _, pools2 = paged_prefill(cfg, recipe, plan, params, pools2,
                                  jnp.asarray(ptrow), jnp.asarray(t1),
                                  jnp.int32(16))
        lg_c, _ = paged_prefill(cfg, recipe, plan, params, pools2,
                                jnp.asarray(ptrow), jnp.asarray(t2),
                                jnp.int32(len(prompt) - 16),
                                start=jnp.int32(16), history=True)
    assert _cos(lg_c[0, -1], lg_m[0, -1]) > 0.999
    assert int(np.argmax(np.asarray(lg_c[0, -1], np.float32))) == \
        int(np.argmax(np.asarray(lg_m[0, -1], np.float32)))


def _mk_engine(cfg, plan, params, **kw):
    from repro.core.recipes import get_recipe as _gr
    from repro.serve.engine import ServeConfig, ServeEngine
    ecfg = ServeConfig(max_batch=4, page_size=8, n_pages=64,
                       max_pages_per_req=8, token_budget=256,
                       prefill_buckets=(16,), fp8_kv=False, **kw)
    return ServeEngine(cfg, _gr("bf16"), plan, params, ecfg), ecfg


def test_engine_chunked_prefill_decode_not_starved(dense_setup):
    """While a long prompt prefills chunk-by-chunk, every already-resident
    request must decode one token per tick (decode is never starved by more
    than the one bounded chunk riding the tick)."""
    from repro.serve.scheduler import Request
    cfg, mesh, plan, params = dense_setup
    eng, ecfg = _mk_engine(cfg, plan, params, prefill_chunk=8)
    r = np.random.default_rng(0)
    short = Request(prompt=list(r.integers(1, cfg.vocab, 4)),
                    max_new_tokens=12)
    long_ = Request(prompt=list(r.integers(1, cfg.vocab, 33)),
                    max_new_tokens=2)
    results = {}
    eng.submit(short)
    assert eng.tick(0.0, results)           # admit + prefill `short`
    st_short = eng.sched.active[0]
    assert st_short.prefilled and len(st_short.generated) == 1
    eng.submit(long_)
    n_chunks = -(-33 // 8)                  # 5 chunks
    for i in range(n_chunks):
        before = len(st_short.generated)
        assert eng.tick(0.0, results)
        st_long = eng.sched.mid_prefill()
        if i < n_chunks - 1:
            assert st_long is not None and st_long.req is long_
            assert st_long.prefill_pos == (i + 1) * 8
            assert not st_long.prefilled   # first token only after last chunk
        else:
            assert eng.sched.mid_prefill() is None
        # the resident decoded exactly one token on EVERY prefill tick
        assert len(st_short.generated) == before + 1
    # long request sampled its first token on the final chunk's tick
    long_st = [s for s in eng.sched.active.values() if s.req is long_]
    assert long_st and len(long_st[0].generated) == 1


def test_engine_chunked_prefill_fcfs_and_completion(dense_setup):
    """Chunked prefill preserves FCFS admission order end-to-end and every
    request completes (prompts longer than the largest bucket included)."""
    from repro.serve.scheduler import Request
    cfg, mesh, plan, params = dense_setup
    eng, _ = _mk_engine(cfg, plan, params, prefill_chunk=16)
    r = np.random.default_rng(2)
    reqs = [Request(prompt=list(r.integers(1, cfg.vocab, n)),
                    max_new_tokens=3)
            for n in (40, 9, 25, 5)]        # 40 > largest bucket (16)
    results = eng.run(reqs, realtime=False)
    assert len(results) == len(reqs)
    for req in reqs:
        assert len(results[req.rid]["tokens"]) == req.max_new_tokens
    # FCFS: first-token order == submission order
    first = sorted(results.items(), key=lambda kv: kv[1]["first_token"])
    assert [rid for rid, _ in first] == [req.rid for req in reqs]
    assert eng.alloc.free_pages == 63       # every page returned


def test_engine_rejects_long_prompt_without_chunking(dense_setup):
    from repro.serve.scheduler import Request
    cfg, mesh, plan, params = dense_setup
    eng, _ = _mk_engine(cfg, plan, params)            # prefill_chunk=None
    with pytest.raises(ValueError, match="prefill_chunk"):
        eng.submit(Request(prompt=[1] * 40, max_new_tokens=2))
    from repro.serve.engine import ServeConfig, ServeEngine
    with pytest.raises(ValueError, match="prefill_chunk"):
        _mk_engine(cfg, plan, params, prefill_chunk=32)   # > largest bucket


# ---------------------------------------------------------------------------
# serve_step unified with the engine's sampling.
# ---------------------------------------------------------------------------
def test_serve_step_unified_sampling_and_per_request_pos(dense_setup):
    from repro.models.lm import init_cache
    from repro.serve.engine import sample_tokens
    from repro.serve.serve_step import make_serve_step
    cfg, mesh, plan, params = dense_setup
    B = 2
    toks = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab, (B, 1)), jnp.int32)

    step = make_serve_step(cfg, recipe := get_recipe("bf16"), plan)
    with mesh:
        # greedy default == engine greedy lane; per-request pos vector honored
        nt_vec, _ = step(params, init_cache(cfg, B, 32), toks,
                         jnp.asarray([2, 2], jnp.int32))
        nt_scl, _ = step(params, init_cache(cfg, B, 32), toks, jnp.int32(2))
    assert nt_vec.shape == (B, 1)
    np.testing.assert_array_equal(np.asarray(nt_vec), np.asarray(nt_scl))

    # stochastic lane routes through engine.sample_tokens (same key => same
    # tokens), greedy rows (temp<=0) stay deterministic
    step_k = make_serve_step(cfg, recipe, plan, top_k=8)
    temps = jnp.asarray([0.0, 1.5], jnp.float32)
    key = jax.random.key(7)
    with mesh:
        from repro.models.lm import decode_step
        lg, _ = decode_step(cfg, recipe, plan, params,
                            init_cache(cfg, B, 32), toks, jnp.int32(2))
        want = sample_tokens(lg[:, -1, :], key, temps, 8)
        got, _ = step_k(params, init_cache(cfg, B, 32), toks, jnp.int32(2),
                        temps=temps, key=key)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(want))
    assert int(got[0, 0]) == int(nt_scl[0, 0])      # greedy row unchanged
