"""The paper's headline accounting (Fig. 2): explicit Q/DQ casts per MoE
forward+backward — 12 (naive drop-in FP8) -> 2 (FP8-Flow-MoE)."""
import os
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from jax.sharding import PartitionSpec as P


from repro.core import casts
from repro.core.linear import expert_ffn, quantize_entry
from repro.core.moe import MoEConfig, moe_block
from repro.core.recipes import get_recipe
from tests.conftest import make_mesh11

EXPECTED_FFN = {"bf16": 0, "blockwise": 8, "naive_fp8": 10, "fp8_flow": 1}
EXPECTED_MOE = {"bf16": 0, "blockwise": 8, "naive_fp8": 12, "fp8_flow": 2}


def _ffn_loss(recipe):
    r = np.random.default_rng(0)
    E, C, K, F = 2, 128, 256, 128
    x = jnp.asarray(r.normal(size=(E, C, K)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    w13 = jnp.asarray(r.normal(size=(E, K, 2 * F)).astype(np.float32) * 0.05)
    w2 = jnp.asarray(r.normal(size=(E, F, K)).astype(np.float32) * 0.05)

    def L(x, w13, w2):
        xi = quantize_entry(recipe, x) if recipe.name == "fp8_flow" else x
        y = expert_ffn(recipe, "swiglu", (), (), xi, w13, w2)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    return L, (x, w13, w2)


@pytest.mark.parametrize("name", list(EXPECTED_FFN))
def test_ffn_cast_count(name):
    recipe = get_recipe(name)
    L, args = _ffn_loss(recipe)
    with casts.ledger() as led:
        jax.grad(L, argnums=(0, 1, 2))(*args)
    n = led.activation_casts()
    # fp8_flow counts the entry quantize here too (no dispatch boundary)
    expected = EXPECTED_FFN[name] + (1 if name == "fp8_flow" else 0)
    assert n == expected, led.summary()


@pytest.mark.parametrize("name", list(EXPECTED_MOE))
def test_moe_block_cast_count(name):
    """Full MoE block (router+dispatch+experts+combine) on a 1x1 mesh."""
    recipe = get_recipe(name)
    mesh = make_mesh11()
    E, D, F, topk, T = 4, 256, 128, 2, 256
    cfg = MoEConfig(n_experts=E, top_k=topk, d_model=D, d_ff=F)
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(T, D)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    wr = jnp.asarray(r.normal(size=(D, E)).astype(np.float32) * 0.02)
    w13 = jnp.asarray(r.normal(size=(E, D, 2 * F)).astype(np.float32) * 0.05)
    w2 = jnp.asarray(r.normal(size=(E, F, D)).astype(np.float32) * 0.05)

    def body(x, wr, w13, w2):
        y, m = moe_block(recipe, cfg, x, wr, w13, w2)
        return jax.lax.psum(jnp.sum(y.astype(jnp.float32) ** 2),
                            ("data", "model"))

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(("data", "model"), None), P(None, None),
                                 P("model", None, None), P("model", None, None)),
                       out_specs=P())
    # the ledger counts trace-time casts: tracing the gradient once is
    # enough, and running shard_map eagerly would take a minute
    with casts.ledger() as led:
        jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(sm(*a)),
                                argnums=(0, 1, 2, 3)))(x, wr, w13, w2)
    assert led.activation_casts() == EXPECTED_MOE[name], led.summary()


def test_masked_fused_epilogue_keeps_two_casts():
    """Masked expert kernels + the fused SwiGLU-in-GEMM-1 epilogue must not
    change the Fig.-2 accounting: still 2 explicit casts (entry + bwd
    island), swiglu_quant stays FUSED kind, and the tag set is identical to
    the unfused fp8_flow FFN."""
    def run(recipe, masked_m):
        r = np.random.default_rng(0)
        E, C, K, F = 2, 128, 256, 128
        x = jnp.asarray(r.normal(size=(E, C, K)).astype(np.float32)
                        ).astype(jnp.bfloat16)
        if masked_m is not None:   # dead dispatch slots carry zeros
            live = jnp.asarray(np.arange(C)[None, :]
                               < np.asarray(masked_m)[:, None])
            x = jnp.where(live[..., None], x, 0)
        w13 = jnp.asarray(r.normal(size=(E, K, 2 * F)).astype(np.float32)
                          * 0.05)
        w2 = jnp.asarray(r.normal(size=(E, F, K)).astype(np.float32) * 0.05)

        def L(x, w13, w2):
            xi = quantize_entry(recipe, x)
            y = expert_ffn(recipe, "swiglu", (), (), xi, w13, w2, masked_m)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        with casts.ledger() as led:
            jax.grad(L, argnums=(0, 1, 2))(x, w13, w2)
        return led

    base = run(get_recipe("fp8_flow", use_pallas=True), None)
    fused = run(get_recipe("fp8_flow", use_pallas=True, masked_experts=True,
                           swiglu_epilogue=True),
                jnp.asarray([64, 128], jnp.int32))
    assert fused.activation_casts() == 2, fused.summary()
    assert fused.activation_casts() == base.activation_casts()
    # same tag set; swiglu_quant present in BOTH, always fused kind
    def tags(led):
        return {(e.kind, e.tag) for e in led.events
                if not e.tag.startswith("q_w")}
    assert tags(fused) == tags(base), (tags(fused), tags(base))
    assert ("fused_quantize", "swiglu_quant") in tags(fused)
    assert not [e for e in fused.events if e.kind == "dequantize"]


def test_flow_has_zero_dequantize_ops():
    """fp8_flow's explicit casts are both QUANTIZE ops — no dequantize ever
    materializes (the casting-free property)."""
    recipe = get_recipe("fp8_flow")
    L, args = _ffn_loss(recipe)
    with casts.ledger() as led:
        jax.grad(L, argnums=(0, 1, 2))(*args)
    explicit_dq = [e for e in led.events if e.kind == "dequantize"]
    assert not explicit_dq


def test_naive_has_double_quant_sites():
    """naive_fp8 must contain the dequantize->requantize pairs the paper
    identifies as the double-quantization-error sites."""
    recipe = get_recipe("naive_fp8")
    L, args = _ffn_loss(recipe)
    with casts.ledger() as led:
        jax.grad(L, argnums=(0, 1, 2))(*args)
    tags = [e.tag for e in led.events if e.kind == "dequantize"]
    assert "dq_transpose" in tags


def test_staged_streaming_backward_stays_two_casts():
    """The staged per-layer backward (DistPlan schedule='stream') keeps
    fp8_flow's Fig.-2 dataflow: per layer, ONE entry quantize (counted once
    more by the remat recompute trace) and ONE backward island quantize —
    no new explicit cast sites, no explicit dequantize; every wire/state
    quantize stays fused-kind."""
    from repro.launch.mesh import make_mesh
    from repro.configs import get_arch
    from repro.data.pipeline import DataConfig, make_batch
    from repro.dist import DistPlan
    from repro.models.lm import ParallelPlan
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import init_train_state, make_train_step

    cfg = get_arch("qwen15_05b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=1e-3)
    recipe = get_recipe("fp8_flow")
    dist = DistPlan(wire="fp8", schedule="stream")
    state = init_train_state(cfg, opt, jax.random.key(0), dist=dist)
    step = make_train_step(cfg, recipe, plan, opt, dist=dist,
                           total_steps=10, warmup_steps=2)
    data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2)
    with mesh, casts.ledger() as led:
        jax.jit(step)(state, make_batch(data, 0))
    by = led.by_tag()
    # the unrolled program has n_layers trace sites: one island quantize per
    # layer backward, one entry quantize per layer forward + one per remat
    # recompute — nothing else on the activation path
    assert by.get(("quantize", "q_bwd_island"), 0) == cfg.n_layers, by
    expected_entry = cfg.n_layers * (2 if cfg.remat else 1)
    assert by.get(("quantize", "q_entry"), 0) == expected_entry, by
    tags = {t for (k, t) in by
            if k in ("quantize", "dequantize") and not t.startswith("q_w")}
    assert tags == {"q_entry", "q_bwd_island"}, by
    assert not [e for e in led.events if e.kind == "dequantize"]
    # the wire + optimizer-state quantizes exist but are FUSED kind
    assert ("fused_quantize", "dp_wire") in by
    assert ("fused_quantize", "opt_state") in by


@pytest.mark.parametrize("policy", ["none", "full", "fp8_resident", "pair"])
def test_streamed_casts_under_every_remat_policy(policy):
    """MemoryPlan extension of the invariant above: the activation-
    residency policy changes WHAT is saved, never the cast structure —
    per layer, ONE backward island quantize under every policy, one entry
    quantize per forward trace (plus one per remat retrace when a policy
    checkpoints), and no new explicit cast tags."""
    import dataclasses
    from repro.launch.mesh import make_mesh
    from repro.configs import get_arch
    from repro.data.pipeline import DataConfig, make_batch
    from repro.dist import DistPlan
    from repro.models.lm import ParallelPlan
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import init_train_state, make_train_step

    cfg = dataclasses.replace(get_arch("qwen15_05b").reduced(),
                              remat_policy=policy)
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=1e-3)
    recipe = get_recipe("fp8_flow")
    dist = DistPlan(wire="fp8", schedule="stream")
    state = init_train_state(cfg, opt, jax.random.key(0), dist=dist)
    step = make_train_step(cfg, recipe, plan, opt, dist=dist,
                           total_steps=10, warmup_steps=2)
    data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2)
    with mesh, casts.ledger() as led:
        jax.jit(step)(state, make_batch(data, 0))
    by = led.by_tag()
    assert by.get(("quantize", "q_bwd_island"), 0) == cfg.n_layers, by
    expected_entry = cfg.n_layers * (1 if policy == "none" else 2)
    assert by.get(("quantize", "q_entry"), 0) == expected_entry, by
    tags = {t for (k, t) in by
            if k in ("quantize", "dequantize") and not t.startswith("q_w")}
    assert tags == {"q_entry", "q_bwd_island"}, by
    assert not [e for e in led.events if e.kind == "dequantize"]
