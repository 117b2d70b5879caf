"""The FP8-native DP wire + ZeRO-1 state (repro.dist): scale agreement,
quantized-reduction parity, FP8 optimizer-state checkpoint round-trip
(including restore onto a different DP mesh size), training parity of the
FP8 wire vs the f32 wire, and the Fig.-2 cast-count invariance.

Multi-replica tests size the mesh to jax.device_count(): run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 (CI does) for real
cross-replica coverage; on one device they degenerate to the P=1 wire."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import checkpointing
from repro.launch.mesh import make_mesh
from repro.configs import get_arch
from repro.core import casts
from repro.core.fp8 import TILE, is_po2
from repro.core.recipes import get_recipe
from repro.data.pipeline import DataConfig, make_batch
from repro.dist import DistPlan, StatePolicy, build_layout
from repro.dist import grad_comm, opt_state, scale_sync
from repro.dist.plan import bucket_flat, bucket_scatter
from repro.launch.sharding import dist_state_specs
from repro.models.lm import ParallelPlan
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import init_train_state, make_train_step


def _dp_mesh(n=None):
    n = n or max(d for d in range(1, jax.device_count() + 1)
                 if jax.device_count() % d == 0)
    return make_mesh((n, 1), ("data", "model")), n


# ---------------------------------------------------------------------------
# Codecs + layout
# ---------------------------------------------------------------------------
def test_exp_i8_codec_exact():
    exps = jnp.arange(-120, 121, dtype=jnp.int8)
    scales = scale_sync.exp_i8_to_scale(exps)
    assert bool(jnp.all(is_po2(scales)))
    back = scale_sync.scale_to_exp_i8(scales)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(exps))


def test_pack_unpack_roundtrip(rng):
    pay = jnp.asarray(rng.normal(size=(16, TILE)), jnp.float8_e4m3fn)
    exp = jnp.asarray(rng.integers(-50, 50, (16, 1)), jnp.int8)
    msg = grad_comm.pack_bucket(pay, exp)
    assert msg.dtype == jnp.uint8 and msg.shape == (16, TILE + 1)
    p2, e2 = grad_comm.unpack_bucket(msg)
    np.testing.assert_array_equal(np.asarray(p2).view(np.uint8),
                                  np.asarray(pay).view(np.uint8))
    np.testing.assert_array_equal(np.asarray(e2), np.asarray(exp))


def test_layout_partitions_every_leaf():
    cfg = get_arch("qwen15_05b").reduced()
    from repro.models.lm import init_params
    params = init_params(cfg, jax.random.key(0))
    plan = DistPlan()
    layout = build_layout(params, plan)
    n_leaves = len(jax.tree.leaves(params))
    slot_idx = [s.index for b in layout.buckets for s in b.slots]
    sens_idx = [i for i, _ in layout.sensitive]
    assert sorted(slot_idx + sens_idx) == list(range(n_leaves))
    assert layout.n_leaves == n_leaves
    # embeddings + norms + biases fall back; big 2D+ weights ride FP8
    sens_names = {p.split(".")[-1] for _, p in layout.sensitive}
    assert "embed" in sens_names
    assert all(n.endswith("_s") or n in ("embed", "bq", "bk", "bv")
               for n in sens_names), sens_names
    for b in layout.buckets:
        assert b.rows % plan.shard_multiple == 0
        offs = [(s.offset_rows, s.offset_rows + s.rows) for s in b.slots]
        for (a0, a1), (b0, _) in zip(offs, offs[1:]):
            assert a1 == b0          # contiguous, non-overlapping


def test_bucket_flat_scatter_roundtrip(rng):
    leaves = [jnp.asarray(rng.normal(size=(4, 100)), jnp.bfloat16),
              jnp.asarray(rng.normal(size=(257,)), jnp.float32),
              jnp.asarray(rng.normal(size=(8, 64)), jnp.bfloat16)]
    from repro.dist.plan import Bucket, LeafSlot
    slots, off = [], 0
    for i, l in enumerate(leaves):
        rows = -(-l.size // TILE)
        slots.append(LeafSlot(index=i, path=f"l{i}", offset_rows=off,
                              rows=rows, size=l.size))
        off += rows
    b = Bucket(rows=off + 3, slots=tuple(slots))   # uneven tail pad
    flat = bucket_flat(b, leaves)
    assert flat.shape == (b.rows, TILE) and flat.dtype == jnp.float32
    out = bucket_scatter(b, flat, leaves)
    for i, l in enumerate(leaves):
        np.testing.assert_array_equal(np.asarray(out[i], np.float32),
                                      np.asarray(l, np.float32))
        assert out[i].dtype == l.dtype


# ---------------------------------------------------------------------------
# Scale agreement + reduction (property tests on the real mesh)
# ---------------------------------------------------------------------------
def test_scale_agreement_identical_buckets(rng):
    """All replicas must produce identically-SCALED buckets — and with
    identical input grads, bit-identical quantized buckets."""
    mesh, n = _dp_mesh()
    rows = 8 * n

    def quant(g):
        pay, exp = grad_comm.quantize_bucket(g[0], "data")
        return pay[None], exp[None]

    f = jax.shard_map(quant, mesh=mesh,
                      in_specs=P("data", None, None),
                      out_specs=(P("data", None, None), P("data", None, None)))
    # different grads per replica -> exponents still agree everywhere
    g_diff = jnp.asarray(rng.normal(size=(n, rows, TILE)) *
                         (10.0 ** rng.integers(-3, 3, (n, 1, 1))),
                         jnp.float32)
    pay, exp = f(g_diff)
    exp = np.asarray(exp)
    assert (exp == exp[:1]).all(), "per-replica scales disagree"
    # scales are the agreed (pmax) po2 of the global amax
    amax = np.abs(np.asarray(g_diff)).max(axis=0).max(-1, keepdims=True)
    want = np.asarray(scale_sync.scale_to_exp_i8(
        jnp.asarray(np.exp2(np.ceil(np.log2(amax / 448.0))))))
    np.testing.assert_array_equal(exp[0], want)
    # identical grads -> identical quantized payload bits
    g_same = jnp.broadcast_to(g_diff[:1], g_diff.shape)
    pay, _ = f(g_same)
    pay = np.asarray(pay).view(np.uint8)
    assert (pay == pay[:1]).all()


@pytest.mark.parametrize("wire", ["fp8", "bf16", "f32"])
def test_reduce_scatter_matches_mean(rng, wire):
    mesh, n = _dp_mesh()
    rows = 8 * n
    g = jnp.asarray(rng.normal(size=(n, rows, TILE)), jnp.float32)

    def red(gl):
        return grad_comm.reduce_scatter_bucket(gl[0], "data", n, wire)

    f = jax.shard_map(red, mesh=mesh, in_specs=P("data", None, None),
                      out_specs=P("data", None))
    got = np.asarray(f(g))                       # (rows, TILE) re-stitched
    want = np.asarray(g).mean(axis=0)
    if wire == "f32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        # one quantization per replica, exact sum: error bounded by the
        # e4m3/bf16 resolution at the (agreed) row amax
        res = 2 ** -3 if wire == "fp8" else 2 ** -8
        amax = np.abs(np.asarray(g)).max(axis=0).max(-1, keepdims=True)
        tol = res * amax * 1.01
        assert (np.abs(got - want) <= tol).all(), \
            np.max(np.abs(got - want) / amax)


# ---------------------------------------------------------------------------
# FP8-split optimizer state: policy encode/decode + AdamW integration
# ---------------------------------------------------------------------------
def test_state_encode_decode_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(3, 200)) * 7.3, jnp.float32)
    for kind, res in [("e4m3", 2 ** -3), ("f16", 2 ** -10)]:
        enc = opt_state.encode(kind, x)
        assert bool(jnp.all(is_po2(enc.scale)))
        dec = opt_state.decode(enc, x.shape, x.size)
        err = np.abs(np.asarray(dec - x))
        amax = np.abs(np.asarray(x)).max()
        assert err.max() <= res * amax * 1.01


def test_adamw_state_policy_dtypes_and_parity(rng):
    params = {"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.bfloat16),
              "norm": jnp.asarray(rng.normal(size=(64,)), jnp.float32)}
    grads = jax.tree.map(
        lambda p: jnp.asarray(rng.normal(size=p.shape) * 1e-2, p.dtype),
        params)
    base = AdamWConfig(lr=1e-2)
    pol = AdamWConfig(lr=1e-2, state_policy=StatePolicy(min_size=1024))
    s0 = adamw.init_state(base, params)
    s1 = adamw.init_state(pol, params)
    # policy state: QTensor e4m3 m / bf16 v / f16 master for the big leaf,
    # classic f32 for the small one
    assert s1["m"]["w"].data.dtype == jnp.float8_e4m3fn
    assert s1["v"]["w"].dtype == jnp.bfloat16
    assert s1["master"]["w"].data.dtype == jnp.float16
    assert s1["m"]["norm"].dtype == jnp.float32
    p0, n0, _ = adamw.apply_updates(base, params, grads, s0)
    p1, n1, _ = adamw.apply_updates(pol, params, grads, s1)
    # the exempt leaf updates identically; the policy leaf within fp8 error
    np.testing.assert_allclose(np.asarray(p1["norm"]), np.asarray(p0["norm"]),
                               rtol=1e-6)
    d = np.abs(np.asarray(p1["w"], np.float32) - np.asarray(p0["w"],
                                                            np.float32))
    assert d.max() < 1e-2 * 0.3          # lr * bounded moment error
    assert n1["m"]["w"].data.dtype == jnp.float8_e4m3fn


def test_state_bytes_model():
    pol = StatePolicy()
    assert opt_state.state_bytes_model(1, pol) < 5.2
    assert opt_state.state_bytes_model(
        1, StatePolicy(m="f32", v="f32", master="f32")) == 12.0


def test_wire_bytes_model_3x():
    n = 10 * 2 ** 20
    fp8 = grad_comm.wire_grad_bytes(n, 8, "fp8")
    bf16_ar = grad_comm.wire_grad_bytes(n, 8, "bf16", mode="none")
    assert bf16_ar / fp8 >= 3.0


# ---------------------------------------------------------------------------
# Checkpoint round-trip: e4m3 moments + po2 scales are bitwise, and restore
# onto a DIFFERENT DP mesh size re-shards the ZeRO-1 flat state.
# ---------------------------------------------------------------------------
def test_fp8_opt_state_checkpoint_bitwise(tmp_path, rng):
    cfg = get_arch("qwen15_05b").reduced()
    opt = AdamWConfig(lr=1e-3)
    dist = DistPlan()
    state = init_train_state(cfg, opt, jax.random.key(1), dist=dist)
    # make the moments non-trivial so the bit check means something
    st = state["opt"]["flat"][0]
    g = jnp.asarray(rng.normal(size=st["v"].shape), jnp.float32)
    state["opt"]["flat"][0]["m"] = opt_state.encode("e4m3", g)
    d = str(tmp_path)
    checkpointing.save(d, 3, state)
    restored, step = checkpointing.restore(d, state)
    assert step == 3

    def bits(x):
        return np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)

    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype, (a.dtype, b.dtype)
        np.testing.assert_array_equal(bits(a), bits(b))

    # restore onto a different DP mesh: values bitwise identical, ZeRO-1
    # flat rows (payload AND scales) sharded over the new data axis
    mesh2, n2 = _dp_mesh()
    sh = {"params": jax.tree.map(lambda _: None, state["params"]),
          "opt": dist_state_specs(mesh2, state["opt"])}
    resharded, _ = checkpointing.restore(d, state, shardings=sh)
    m2 = resharded["opt"]["flat"][0]["m"]
    np.testing.assert_array_equal(bits(m2.data),
                                  bits(state["opt"]["flat"][0]["m"].data))
    np.testing.assert_array_equal(np.asarray(m2.scale),
                                  np.asarray(state["opt"]["flat"][0]
                                             ["m"].scale))
    want = dist_state_specs(mesh2, state["opt"])["flat"][0]["m"]
    assert m2.data.sharding == want.data
    assert m2.scale.sharding == want.scale


# ---------------------------------------------------------------------------
# End-to-end: FP8-reduced vs f32-reduced training parity (the ISSUE gate)
# and the Fig.-2 cast-count invariance under the new wire.
# ---------------------------------------------------------------------------
def _train(cfg, mesh, dist, n_steps, lr=3e-3, seed=0):
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=lr)
    recipe = get_recipe("fp8_flow")
    state = init_train_state(cfg, opt, jax.random.key(seed), dist=dist)
    step = jax.jit(make_train_step(cfg, recipe, plan, opt, dist=dist,
                                   total_steps=400, warmup_steps=5))
    data = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    losses = []
    with mesh:
        for i in range(n_steps):
            state, m = step(state, make_batch(data, i))
            losses.append(float(m["loss"]))
    return np.array(losses), state


def test_fp8_vs_f32_wire_training_parity():
    """20 steps on qwen15_05b: FP8-reduced loss within 1% of f32-reduced."""
    cfg = get_arch("qwen15_05b").reduced()
    mesh, _ = _dp_mesh()
    l_fp8, _ = _train(cfg, mesh, DistPlan(wire="fp8"), 20)
    l_f32, _ = _train(cfg, mesh, DistPlan(
        wire="f32", policy=StatePolicy(m="f32", v="f32", master="f32")), 20)
    assert np.isfinite(l_fp8).all() and np.isfinite(l_f32).all()
    # both learn
    assert l_fp8[-5:].mean() < l_fp8[:3].mean() - 0.1
    rel = abs(l_fp8[-5:].mean() - l_f32[-5:].mean()) / l_f32[-5:].mean()
    assert rel < 0.01, f"fp8 vs f32 wire diverged: {rel:.4f}"
    # per-step tracking, not just the endpoint
    assert np.max(np.abs(l_fp8 - l_f32) / np.abs(l_f32)) < 0.05


def test_cast_count_unchanged_with_wire():
    """The DP wire must not add explicit casts: fp8_flow stays at 2 per FFN
    (entry quantize fwd + island quantize bwd); all wire quantizes are
    fused-kind ('dp_wire'/'opt_state' tags)."""
    cfg = get_arch("qwen15_05b").reduced()
    mesh, _ = _dp_mesh(1)
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=1e-3)
    recipe = get_recipe("fp8_flow")
    data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2)
    batch = make_batch(data, 0)

    counts, tagsets = {}, {}
    for name, dist in [("legacy", None), ("fp8_wire", DistPlan())]:
        state = init_train_state(cfg, opt, jax.random.key(0), dist=dist)
        step = make_train_step(cfg, recipe, plan, opt, dist=dist,
                               total_steps=10, warmup_steps=2)
        # jit: the ledger records at trace time, and eager shard_map cannot
        # evaluate the remat'd layer scan on this jax version
        with mesh, casts.ledger() as led:
            jax.jit(step)(state, batch)
        counts[name] = led.activation_casts()
        tags = led.by_tag()
        tagsets[name] = {t for (k, t) in tags
                         if k in ("quantize", "dequantize")
                         and not t.startswith("q_w")}
        if dist is not None:
            # the wire + opt-state quantizes exist but are FUSED kind
            assert ("fused_quantize", "dp_wire") in tags, tags
            assert ("fused_quantize", "opt_state") in tags, tags
    # zero additional explicit casts, and the fp8_flow dataflow stays the
    # paper's 2-per-FFN: entry quantize (fwd) + island quantize (bwd)
    assert counts["fp8_wire"] == counts["legacy"], counts
    assert tagsets["fp8_wire"] == tagsets["legacy"] \
        == {"q_entry", "q_bwd_island"}, tagsets


def test_moe_arch_through_fp8_wire():
    """The wire's replica-local forward takes the new EP=1 local MoE path
    (core/moe.py ep_axis=None identity collectives + shared-expert add):
    a MoE arch with shared experts must train end-to-end."""
    cfg = get_arch("deepseek_v2_lite").reduced()
    assert cfg.moe and cfg.n_shared_experts
    mesh, _ = _dp_mesh()
    losses, state = _train(cfg, mesh, DistPlan(wire="fp8"), 3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] + 0.1
    # expert weights rode the FP8 bucket wire (not the bf16 fallback)
    layout = build_layout(state["params"], DistPlan())
    bucket_names = {s.path.split(".")[-1]
                    for b in layout.buckets for s in b.slots}
    assert {"we13", "we2"} <= bucket_names
    sens_names = {p.split(".")[-1] for _, p in layout.sensitive}
    assert "w_router" in sens_names


def test_dist_rejects_model_parallel_mesh():
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    cfg = get_arch("qwen15_05b").reduced()
    mesh = make_mesh((1, 2), ("data", "model"))
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    with pytest.raises(ValueError, match="model-parallel"):
        make_train_step(cfg, get_recipe("fp8_flow"), plan, AdamWConfig(),
                        dist=DistPlan())


# ---------------------------------------------------------------------------
# Streaming wire (schedule='stream'): layer-aligned reverse-order buckets,
# parity vs the post-hoc wire, the in-backward issue order, and the fast
# clear errors when a configuration cannot stream.
# ---------------------------------------------------------------------------
def test_layered_layout_partitions_and_reverse_orders():
    """Layered buckets cover every leaf per layer, never span a layer
    boundary, and are emitted in the staged backward's order: main stack
    last-layer-first, then the dense prologue last-first."""
    cfg = get_arch("deepseek_v2_lite").reduced()
    from repro.models.lm import init_params
    params = init_params(cfg, jax.random.key(0))
    plan = DistPlan(schedule="stream")
    layout = build_layout(params, plan)
    leaves = jax.tree.leaves(params)
    slot_idx = {s.index for b in layout.buckets for s in b.slots}
    sens_idx = {i for i, _ in layout.sensitive}
    assert slot_idx | sens_idx == set(range(len(leaves)))
    assert not (slot_idx & sens_idx)
    # every bucket belongs to exactly one (stack, layer)
    for b in layout.buckets:
        assert b.stack is not None and b.layer is not None
        assert all(s.layer == b.layer for s in b.slots)
        assert b.rows % plan.shard_multiple == 0
    # reverse emission order: 'layers' L-1..0 before 'dense_layers' nd-1..0
    keys = [(b.stack, b.layer) for b in layout.buckets]
    main = [l for s, l in keys if s == "layers"]
    dense = [l for s, l in keys if s == "dense_layers"]
    assert main == sorted(main, reverse=True) and main[0] == max(main)
    assert dense == sorted(dense, reverse=True)
    assert keys.index(("layers", main[-1])) < keys.index(
        ("dense_layers", dense[0]))
    # each stacked eligible leaf appears once per layer
    from collections import Counter
    per = Counter(s.index for b in layout.buckets for s in b.slots)
    n_main = cfg.n_layers - cfg.n_dense_layers
    for i, n in per.items():
        path = [s.path for b in layout.buckets for s in b.slots
                if s.index == i][0]
        want = n_main if path.startswith("layers.") else cfg.n_dense_layers
        assert n == want, (path, n, want)


def test_layered_bucket_flat_scatter_roundtrip():
    """Per-layer slots slice the stacked leaf; scatter + restack is exact."""
    cfg = get_arch("qwen15_05b").reduced()
    from repro.models.lm import init_params
    params = init_params(cfg, jax.random.key(0))
    layout = build_layout(params, DistPlan(schedule="stream"))
    leaves = jax.tree.leaves(params)
    stacked = {}
    for b in layout.buckets:
        flat = bucket_flat(b, leaves)
        assert flat.shape == (b.rows, TILE)
        for key, piece in bucket_scatter(b, flat, leaves).items():
            assert isinstance(key, tuple)
            stacked.setdefault(key[0], {})[key[1]] = piece
    for i, pieces in stacked.items():
        re = jnp.stack([pieces[l] for l in range(leaves[i].shape[0])])
        assert re.dtype == leaves[i].dtype
        np.testing.assert_array_equal(np.asarray(re, np.float32),
                                      np.asarray(leaves[i], np.float32))


def test_streaming_matches_posthoc_wire():
    """Reverse-order-bucket parity: schedule='stream' vs schedule='posthoc'
    over the SAME layered layout — identical buckets and quantization
    groups, only the issue order differs, so the loss curves and updated
    params must agree to reduction-order noise."""
    cfg = get_arch("qwen15_05b").reduced()
    mesh, _ = _dp_mesh()
    l_s, st_s = _train(cfg, mesh, DistPlan(wire="fp8", schedule="stream"), 5)
    l_p, st_p = _train(cfg, mesh, DistPlan(wire="fp8", layered=True), 5)
    assert np.isfinite(l_s).all()
    np.testing.assert_allclose(l_s, l_p, rtol=1e-3)
    for a, b in zip(jax.tree.leaves(st_s["params"]),
                    jax.tree.leaves(st_p["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_streaming_moe_arch_trains():
    """Streaming through a MoE arch with a dense prologue + shared experts
    (per-layer buckets over we13/we2/ws13/ws2, dense stack streamed after
    the main stack)."""
    cfg = get_arch("deepseek_v2_lite").reduced()
    mesh, _ = _dp_mesh()
    losses, state = _train(cfg, mesh, DistPlan(wire="fp8",
                                               schedule="stream"), 3)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] + 0.1
    layout = build_layout(state["params"], DistPlan(schedule="stream"))
    bucket_names = {s.path.split(".")[-1]
                    for b in layout.buckets for s in b.slots}
    assert {"we13", "we2", "ws13", "ws2"} <= bucket_names


def test_streaming_fp8_vs_f32_training_parity():
    """The acceptance gate: 20 steps, fp8-STREAMING loss curve within 1% of
    the f32 post-hoc wire (same tolerance the PR-3 wire holds)."""
    cfg = get_arch("qwen15_05b").reduced()
    mesh, _ = _dp_mesh()
    l_fp8, _ = _train(cfg, mesh, DistPlan(wire="fp8", schedule="stream"), 20)
    l_f32, _ = _train(cfg, mesh, DistPlan(
        wire="f32", policy=StatePolicy(m="f32", v="f32", master="f32")), 20)
    assert np.isfinite(l_fp8).all() and np.isfinite(l_f32).all()
    assert l_fp8[-5:].mean() < l_fp8[:3].mean() - 0.1
    rel = abs(l_fp8[-5:].mean() - l_f32[-5:].mean()) / l_f32[-5:].mean()
    assert rel < 0.01, f"fp8 streaming vs f32 wire diverged: {rel:.4f}"
    assert np.max(np.abs(l_fp8 - l_f32) / np.abs(l_f32)) < 0.05


def test_streaming_jaxpr_issues_rs_inside_backward():
    """The structural check: in the streaming step's jaxpr, at least one
    bucket reduce-scatter (all_to_all) is issued BEFORE the last backward
    GEMM; the post-hoc step issues every one after."""
    if jax.device_count() < 2:
        pytest.skip("P=1 elides the collective "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    cfg = get_arch("qwen15_05b").reduced()
    mesh, n = _dp_mesh()
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=1e-3)
    recipe = get_recipe("fp8_flow")
    data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=max(n, 2))
    batch = make_batch(data, 0)

    def jaxpr_of(dist):
        state = init_train_state(cfg, opt, jax.random.key(0), dist=dist)
        step = make_train_step(cfg, recipe, plan, opt, dist=dist,
                               total_steps=10, warmup_steps=2)
        return str(jax.make_jaxpr(step)(state, batch))

    jx_s = jaxpr_of(DistPlan(wire="fp8", schedule="stream"))
    jx_p = jaxpr_of(DistPlan(wire="fp8", layered=True))
    assert jx_s.count("all_to_all") == jx_p.count("all_to_all") > 0
    assert jx_s.find("all_to_all") < jx_s.rfind("dot_general"), \
        "streaming wire: no reduce-scatter before the last backward GEMM"
    assert jx_p.find("all_to_all") > jx_p.rfind("dot_general"), \
        "post-hoc wire unexpectedly interleaved (baseline drifted)"


def test_streaming_fast_clear_errors():
    cfg = get_arch("qwen15_05b").reduced()
    mesh, _ = _dp_mesh(1)
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    # schedule='stream' forces layer-aligned buckets
    with pytest.raises(ValueError, match="layer-aligned"):
        DistPlan(schedule="stream", layered=False)
    # encoder-decoder archs keep the post-hoc wire
    enc = get_arch("seamless_m4t_v2").reduced()
    with pytest.raises(ValueError, match="decoder-only"):
        make_train_step(enc, get_recipe("fp8_flow"), plan, AdamWConfig(),
                        dist=DistPlan(schedule="stream"))
    # the launcher-facing probe reports a reason instead of raising; grad
    # accumulation no longer blocks streaming (local accumulation + one
    # wire pass on the last microbatch)
    from repro.dist import streaming_fallback_reason
    assert streaming_fallback_reason(enc) is not None
    assert streaming_fallback_reason(cfg) is None
    assert streaming_fallback_reason(cfg, grad_accum=4) is None


def test_layered_sensitive_leaves_carry_stack_tags():
    """Satellite: GradLayout.sensitive gains layer (stack) tags — stacked
    sensitive leaves (norm scales, per-layer routers) are marked so the
    streaming backward can issue each layer's bf16 psum with its bucket;
    the ends (embed / final norm / head) stay untagged (post-hoc)."""
    cfg = get_arch("deepseek_v2_lite").reduced()
    from repro.models.lm import init_params
    params = init_params(cfg, jax.random.key(0))
    layout = build_layout(params, DistPlan(schedule="stream"))
    by_name = {s.path.split(".")[-1]: s for s in layout.sensitive}
    assert by_name["w_router"].stack == "layers"
    assert by_name["ln1_s"].stack in ("layers", "dense_layers")
    assert by_name["embed"].stack is None
    assert by_name["final_norm_s"].stack is None
    # the flat (non-layered) layout carries no tags
    flat_layout = build_layout(params, DistPlan())
    assert all(s.stack is None for s in flat_layout.sensitive)
    # legacy 2-tuple iteration still works
    for i, p in layout.sensitive:
        assert isinstance(i, int) and isinstance(p, str)


def _train_accum(cfg, mesh, dist, n_steps, grad_accum, lr=3e-3, seed=0):
    """_train with a leading microbatch axis on every batch."""
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=lr)
    recipe = get_recipe("fp8_flow")
    state = init_train_state(cfg, opt, jax.random.key(seed), dist=dist)
    step = jax.jit(make_train_step(cfg, recipe, plan, opt, dist=dist,
                                   grad_accum=grad_accum, total_steps=400,
                                   warmup_steps=5))
    # per-MICROBATCH rows must divide the DP axis
    data = DataConfig(vocab=cfg.vocab, seq_len=64,
                      global_batch=grad_accum * jax.device_count())
    losses = []
    with mesh:
        for i in range(n_steps):
            b = make_batch(data, i)
            if grad_accum > 1:
                b = jax.tree.map(lambda a: a.reshape(
                    grad_accum, a.shape[0] // grad_accum, *a.shape[1:]), b)
            state, m = step(state, b)
            losses.append(float(m["loss"]))
    return np.array(losses), state


def test_stream_grad_accum_matches_posthoc():
    """Satellite: grad-accum streaming — microbatch grads accumulate
    locally, ONE quantize + reduce-scatter per bucket on the last
    microbatch.  Must match the post-hoc wire over the same layered layout
    (identical buckets and quantization groups) to reduction-order noise,
    and the single-microbatch stream result."""
    cfg = get_arch("qwen15_05b").reduced()
    mesh, _ = _dp_mesh()
    l_s, st_s = _train_accum(cfg, mesh, DistPlan(wire="fp8",
                                                 schedule="stream"), 5, 2)
    l_p, st_p = _train_accum(cfg, mesh, DistPlan(wire="fp8", layered=True),
                             5, 2)
    assert np.isfinite(l_s).all()
    np.testing.assert_allclose(l_s, l_p, rtol=1e-3)
    for a, b in zip(jax.tree.leaves(st_s["params"]),
                    jax.tree.leaves(st_p["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=2e-2)


def test_stream_grad_accum_single_wire_pass_jaxpr():
    """With grad_accum=2 the streaming step still issues exactly ONE fused
    reduce-scatter per bucket (not one per microbatch), and it stays
    interleaved with backward GEMMs."""
    if jax.device_count() < 2:
        pytest.skip("P=1 elides the collective")
    cfg = get_arch("qwen15_05b").reduced()
    mesh, n = _dp_mesh()
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=1e-3)
    recipe = get_recipe("fp8_flow")
    dist = DistPlan(wire="fp8", schedule="stream")
    state = init_train_state(cfg, opt, jax.random.key(0), dist=dist)
    layout = build_layout(state["params"], dist)
    step = make_train_step(cfg, recipe, plan, opt, dist=dist, grad_accum=2,
                           total_steps=10, warmup_steps=2)
    data = DataConfig(vocab=cfg.vocab, seq_len=32,
                      global_batch=2 * max(n, 2))
    b = jax.tree.map(lambda a: a.reshape(2, a.shape[0] // 2, *a.shape[1:]),
                     make_batch(data, 0))
    jx = str(jax.make_jaxpr(step)(state, b))
    assert jx.count("all_to_all") == len(layout.buckets), \
        (jx.count("all_to_all"), len(layout.buckets))
    assert jx.find("all_to_all") < jx.rfind("dot_general"), \
        "accumulated streaming wire not interleaved with the backward"


@pytest.mark.parametrize("policy", ["fp8_resident", "pair"])
def test_stream_composes_with_remat_policy(policy):
    """Satellite compose test: the streaming wire under each MemoryPlan
    policy — per-block vjp granularity changes ('pair' streams two-layer
    blocks) but the math must match the post-hoc wire at the loss-curve
    level."""
    import dataclasses as dc
    cfg = dc.replace(get_arch("qwen15_05b").reduced(), remat_policy=policy)
    mesh, _ = _dp_mesh()
    l_s, _ = _train(cfg, mesh, DistPlan(wire="fp8", schedule="stream"), 5)
    l_p, _ = _train(cfg, mesh, DistPlan(wire="fp8", layered=True), 5)
    assert np.isfinite(l_s).all()
    np.testing.assert_allclose(l_s, l_p, rtol=1e-3)


def test_staged_forward_matches_scan():
    """ParallelPlan.stage_layers runs the decoder through the unrolled
    staged program (_run_stack_unrolled, two-layer carry window) — same
    function as the monolithic scan."""
    from repro.data.pipeline import make_batch as mk
    from repro.models.lm import forward
    cfg = get_arch("deepseek_v2_lite").reduced()
    from repro.models.lm import init_params
    params = init_params(cfg, jax.random.key(0))
    plan_scan = ParallelPlan(mesh=None, dp_axes=(), shard_map_mlp=False)
    plan_staged = ParallelPlan(mesh=None, dp_axes=(), shard_map_mlp=False,
                               stage_layers=True)
    batch = mk(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2), 0)
    recipe = get_recipe("fp8_flow")
    l0, m0 = jax.jit(lambda p, b: forward(cfg, recipe, plan_scan, p, b))(
        params, batch)
    l1, m1 = jax.jit(lambda p, b: forward(cfg, recipe, plan_staged, p, b))(
        params, batch)
    # same math, different fusion groups (scan body vs unrolled layers):
    # bf16 forward rounding differs at ~1e-4 relative
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-3)
    np.testing.assert_allclose(float(m1["aux_loss"]), float(m0["aux_loss"]),
                               rtol=1e-3, atol=1e-6)


def test_grad_accum_keeps_forward_metrics():
    """_local_grads used to return {} for grad_accum > 1 — forward metrics
    must now be accumulated and averaged like the loss."""
    cfg = get_arch("deepseek_v2_lite").reduced()
    plan = ParallelPlan(mesh=None, dp_axes=(), shard_map_mlp=False)
    opt = AdamWConfig(lr=1e-3)
    state = init_train_state(cfg, opt, jax.random.key(0))
    data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
    flat = make_batch(data, 0)
    micro = jax.tree.map(lambda a: a.reshape(2, 2, *a.shape[1:]), flat)
    step = jax.jit(make_train_step(cfg, get_recipe("fp8_flow"), plan, opt,
                                   grad_accum=2, total_steps=10,
                                   warmup_steps=2))
    _, metrics = step(state, micro)
    assert "aux_loss" in metrics, metrics.keys()
    assert np.isfinite(float(metrics["aux_loss"]))
    assert np.isfinite(float(metrics["loss"]))
