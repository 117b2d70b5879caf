"""FLOP and parameter counters against hand counts of DeepSeek-V2-Lite.

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
