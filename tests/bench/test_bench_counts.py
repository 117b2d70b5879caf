"""FLOP and parameter counters against hand counts of DeepSeek-V2-Lite, of
a share of its routed experts, and of DeepSeek-V3's share on one chip.

Hand counts (d 2048, 16 heads of 128, dense FFN 10880 as the repo's model
file has it, 64 experts of 1408, top-6, 2 shared, vocabulary 12,800):
1 dense + 1 MoE layer hold 723.8M parameters, 1 dense + 4 MoE 2.49B; a
token multiplies through 196M (attention 33.6M, dense FFN 66.9M, 6 routed
+ 2 shared experts 69.2M, head 26.2M); with causal attention at S=4096 a
training token costs 1.28 GFLOP; and 49,152 routed rows (B=2 x S=4096 x 6)
cost 2.55 TFLOP of expert FFN forward and backward.
"""
import json

import pytest

import bench_tiny
from bench import counts


def cfg(name, d_ff=None):
    with open(f"{bench_tiny.ROOT}/bench/configs/{name}.json") as f:
        c = json.load(f)
    if d_ff:
        c["intermediate_size"] = d_ff
    return c


@pytest.mark.parametrize("name,want", [("dsv2lite-train-l2", 723.8e6),
                                       ("dsv2lite-serve-l5", 2.487e9)])
def test_params_hand_count(name, want):
    assert counts.n_params(cfg(name, 10880)) == pytest.approx(want, rel=2e-3)


def test_params_as_configured():
    c = cfg("dsv2lite-train-l2")
    assert counts.n_params(c) == c["params_total"]
    # the published dense width adds 3 * 2048 * 64 parameters
    assert counts.n_params(c) - counts.n_params(cfg("dsv2lite-train-l2",
                                                    10880)) == 3 * 2048 * 64


def test_active_params_hand_count():
    c = cfg("dsv2lite-train-l2", 10880)
    parts = 33.6e6 + 66.9e6 + 69.2e6 + 26.2e6
    assert counts.active_params(c) == pytest.approx(parts, rel=2e-3)
    assert counts.train_flops_per_token(c, 4096) == pytest.approx(1.28e9,
                                                                  rel=5e-3)


def test_routed_expert_work_per_step():
    c = cfg("dsv2lite-train-l2")
    rows = 2 * 4096 * 6
    fwd_bwd = 3 * counts.routed_expert_flops(c, rows)
    assert fwd_bwd == pytest.approx(2.55e12, rel=5e-3)
    scope = counts.train_expert_scope(c, 2 * 4096, rows)
    assert scope["flops"] == pytest.approx(
        fwd_bwd + 3 * 2 * 8192 * 3 * 2048 * 10944)


def test_serve_counts():
    c = cfg("dsv2lite-serve-l5")
    # 4 MoE layers x 64 experts x 3 x 2048 x 1408 e4m3 bytes + f32 scales
    per = 3 * 2048 * 1408
    assert counts.w8_expert_bytes(c) == 4 * 64 * (per + 4 * per // 16384)
    assert counts.serve_token_flops(c, 0) == 2 * counts.active_params(c)
    assert counts.attn_score_flops(c, 100) == 4 * 100 * 16 * 128 * 5


# the parent commit's counts, before a configuration could hold a share
PINNED = {
    "dsv2lite-train-l2": dict(
        n_params=724314112, active_params=196345856,
        serve_token_flops=392691712, w8_expert_bytes=553783296,
        scope_flops=5856187908096, scope_bytes=4967104512),
    "dsv2lite-serve-l5": dict(
        n_params=2487900160, active_params=454688768,
        serve_token_flops=909377536, w8_expert_bytes=2215133184,
        scope_flops=5856187908096, scope_bytes=18254659584),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_existing_configs_count_as_before(name):
    c = cfg(name)
    scope = counts.train_expert_scope(c, 2 * 4096, 2 * 4096 * 6)
    assert dict(n_params=counts.n_params(c),
                active_params=counts.active_params(c),
                serve_token_flops=counts.serve_token_flops(c, 0),
                w8_expert_bytes=counts.w8_expert_bytes(c),
                scope_flops=scope["flops"],
                scope_bytes=scope["bytes"]) == PINNED[name]


def share(c, held=4, published=16, first=4):
    c = dict(c, n_routed_experts=held, first_routed_expert=first)
    c["published"] = dict(c["published"], n_routed_experts=published)
    return c


def test_share_hand_count():
    """4 of 16 experts held: a 2048 x 16 router, 4 x 3 x 2048 x 1408
    expert parameters a layer, and 6 x 4/16 = 1.5 routed experts of work a
    token."""
    whole = cfg("dsv2lite-train-l2")
    c = share(whole)
    D, Fe, nm = 2048, 1408, 1
    per = 3 * D * Fe
    assert counts.routed_experts(c) == (16, range(4, 8))
    base = counts.n_params(whole) - nm * (D * 64 + 64 * per)
    assert counts.n_params(c) == base + nm * (D * 16 + 4 * per)
    base = counts.active_params(whole) - nm * (D * 64 + 6 * per)
    assert counts.active_params(c) == base + nm * (D * 16 + 1.5 * per)
    assert counts.serve_token_flops(c, 0) == 2 * counts.active_params(c)
    assert counts.w8_expert_bytes(c) == nm * 4 * (per + 4 * per // 16384)
    assert counts.train_expert_scope(c, 8192, 100)["bytes"] == \
        (nm * 4 * per + 3 * D * 10944) * 8
    with pytest.raises(ValueError):
        counts.routed_experts(share(whole, first=13))


def test_deepseek_v3_share_hand_count():
    """DeepSeek-V3's serving share on one chip (8 of 256 routed experts of
    2048 at hidden 7168, 1 dense + 4 MoE layers): 44,040,192 parameters an
    expert, 2,688 128x128 blocks each with an f32 scale; a token's 8 picks
    land 8 x 8/256 = 0.25 experts here; the router is 7168 x 256."""
    c = {"hidden_size": 7168, "num_attention_heads": 128,
         "num_key_value_heads": 128, "arch": {"head_dim": 128},
         "vocab_size": 16160, "intermediate_size": 18432,
         "moe_intermediate_size": 2048, "n_routed_experts": 8,
         "num_experts_per_tok": 8, "n_shared_experts": 1,
         "first_k_dense_replace": 1, "num_hidden_layers": 5,
         "published": {"n_routed_experts": 256}}
    per = 3 * 7168 * 2048
    assert per == 44040192 and per // (128 * 128) == 2688
    assert counts.w8_expert_bytes(c) == 4 * 8 * (44040192 + 4 * 2688)
    none = dict(c, num_experts_per_tok=0)
    assert counts.active_params(c) - counts.active_params(none) == \
        4 * 0.25 * per
    assert counts.n_params(c) - counts.n_params(dict(c, n_routed_experts=0)) \
        == 4 * 8 * per
    # the router scores all 256: 4 layers x 7168 x (256 - 8) more than 8
    held_only = dict(c, published={"n_routed_experts": 8})
    assert counts.n_params(c) - counts.n_params(held_only) == 4 * 7168 * 248
