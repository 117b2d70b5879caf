"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and value distributions."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.fp8 import TILE
from repro.core.quant import QTensor, quantize, _dequantize_nocount
from repro.kernels import interpret_mode, ops, ref


def _bits(a):
    return np.asarray(a).view(np.uint8)


def _x(seed, *shape, spread=1.5):
    r = np.random.default_rng(seed)
    return jnp.asarray((r.normal(size=shape)
                        * np.exp(r.normal(size=shape) * spread)
                        ).astype(np.float32))


@pytest.mark.parametrize("shape", [(128, 128), (256, 384), (384, 256)])
@pytest.mark.parametrize("seed", [0, 1])
def test_quantize_kernel(shape, seed):
    x = _x(seed, *shape)
    q = ops.quantize_rowwise(x)
    dr, sr = ref.quantize_rowwise_ref(x)
    assert np.array_equal(_bits(q.data), _bits(dr))
    assert np.array_equal(np.asarray(q.scale), np.asarray(sr))


@pytest.mark.parametrize("shape", [(128, 128), (256, 128), (128, 256),
                                   (384, 384)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fp8_transpose_kernel_bit_exact(shape, seed):
    """The integer exponent-rebase kernel must match the po2-multiply oracle
    BIT FOR BIT (including RNE shifts into the subnormal range)."""
    x = _x(seed, *shape, spread=2.5)
    q = ops.quantize_rowwise(x)
    qt = ops.fp8_transpose(q)
    dr, sr = ref.fp8_transpose_ref(q.data, q.scale)
    assert np.array_equal(_bits(qt.data), _bits(dr))
    assert np.array_equal(np.asarray(qt.scale), np.asarray(sr))


def test_fp8_transpose_subnormal_edge():
    """Force large scale spread within a block so re-basing shifts values
    deep into (and past) the subnormal range."""
    r = np.random.default_rng(3)
    x = r.normal(size=(128, 128)).astype(np.float32)
    x[::2] *= 2.0 ** 12    # alternate rows huge -> s_max >> s of small rows
    x[1::2] *= 2.0 ** -10
    q = ops.quantize_rowwise(jnp.asarray(x))
    qt = ops.fp8_transpose(q)
    dr, sr = ref.fp8_transpose_ref(q.data, q.scale)
    assert np.array_equal(_bits(qt.data), _bits(dr))


@pytest.mark.parametrize("m,f", [(128, 128), (256, 256), (128, 384)])
def test_fused_swiglu_quant_kernel(m, f):
    h = _x(11, m, 2 * f, spread=0.5).astype(jnp.bfloat16)
    q = ops.fused_swiglu_quant(h)
    dr, sr = ref.fused_swiglu_quant_ref(h)
    assert np.array_equal(_bits(q.data), _bits(dr))
    assert np.array_equal(np.asarray(q.scale), np.asarray(sr))


@pytest.mark.parametrize("e,c,k,n", [(2, 128, 128, 128), (4, 128, 256, 128),
                                     (1, 256, 384, 256)])
def test_grouped_gemm_kernel(e, c, k, n):
    x = _x(5, e, c, k, spread=0.5)
    w = _x(6, e, k, n, spread=0.3) * 0.05
    qx = quantize(x, (1, 1, TILE), tag="t")
    qw = quantize(w, (1, TILE, TILE), tag="t")
    out_k = ops.grouped_gemm_fp8(qx, qw)
    out_r = ref.grouped_gemm_fp8_ref(qx.data, qx.scale, qw.data, qw.scale)
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32),
                               rtol=2e-2, atol=2e-2)
    # against the dequantized ground truth (same math, unordered sum)
    gt = np.einsum("eck,ekn->ecn",
                   np.asarray(_dequantize_nocount(qx, jnp.float32)),
                   np.asarray(_dequantize_nocount(qw, jnp.float32)))
    rel = np.abs(np.asarray(out_k, np.float32) - gt) / (np.abs(gt) + 1e-2)
    assert rel.mean() < 2e-2


@pytest.mark.parametrize("e,m,n,c", [(2, 128, 128, 128), (1, 256, 128, 256)])
def test_grouped_gemm_nt_kernel(e, m, n, c):
    a = _x(7, e, m, c, spread=0.5)
    b = _x(8, e, n, c, spread=0.5) * 0.1
    qa = quantize(a, (1, 1, TILE), tag="t")
    qb = quantize(b, (1, 1, TILE), tag="t")
    out_k = ops.grouped_gemm_nt_fp8(qa, qb)
    out_r = ref.grouped_gemm_nt_fp8_ref(qa.data, qa.scale, qb.data, qb.scale)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-2, atol=2e-2)


def test_grouped_gemm_quant_out_kernel():
    e, c, k, n = 2, 128, 256, 128
    x = _x(9, e, c, k, spread=0.5)
    w = _x(10, e, k, n, spread=0.3) * 0.05
    qx = quantize(x, (1, 1, TILE), tag="t")
    qw = quantize(w, (1, TILE, TILE), tag="t")
    q_k = ops.grouped_gemm_fp8_quant_out(qx, qw)
    dr, sr = ref.grouped_gemm_fp8_quant_out_ref(qx.data, qx.scale,
                                                qw.data, qw.scale)
    assert np.array_equal(_bits(q_k.data), _bits(dr))
    assert np.array_equal(np.asarray(q_k.scale), np.asarray(sr))


@pytest.mark.parametrize("t,d,n_out", [(32, 256, 48), (64, 128, 64),
                                       (16, 128, 40)])
def test_fused_permute_pad_kernel(t, d, n_out):
    r = np.random.default_rng(12)
    x = jnp.asarray(r.normal(size=(t, d))).astype(jnp.float8_e4m3fn)
    sc = jnp.asarray(np.exp2(r.integers(-8, 8, (t, d // TILE))
                             ).astype(np.float32))
    row_map = np.full(n_out, -1, np.int32)
    perm = r.permutation(t)[:min(t, n_out)]
    row_map[:len(perm)] = perm
    row_map = jnp.asarray(row_map)
    q = QTensor(data=x, scale=sc, tile=(1, TILE))
    out = ops.fused_permute_pad(q, row_map, n_out)
    xr, sr = ref.fused_permute_pad_ref(x, sc, row_map, n_out)
    assert np.array_equal(_bits(out.data), _bits(xr))
    assert np.array_equal(np.asarray(out.scale), np.asarray(sr))


# ---------------------------------------------------------------------------
# Masked grouped-GEMM layout: skewed-routing parity vs the padded kernels,
# tile-granular oracle semantics, alignment padding, and metadata contracts.
# ---------------------------------------------------------------------------
def _skew(kind, E, C, seed=0):
    """Per-expert live-row counts for the routing-skew patterns."""
    r = np.random.default_rng(seed)
    mm = {"zero_expert": [0] + [C] * (E - 1),
          "all_to_one": [C] + [0] * (E - 1),
          "random": list(r.integers(0, C + 1, E))}[kind]
    return jnp.asarray(np.asarray(mm, np.int32))


def _zero_dead_rows(q, mm):
    """Zero payload rows beyond each expert's count (scale -> 1.0) — the
    dispatch-layout invariant the masked kernels rely on for bitwise parity."""
    E, C = q.data.shape[:2]
    live = jnp.asarray(np.arange(C)[None, :] < np.asarray(mm)[:, None])
    data = jnp.where(live[..., None], q.data.astype(jnp.float32),
                     0.0).astype(q.data.dtype)
    scale = jnp.where(live[..., None], q.scale, 1.0)
    return QTensor(data, scale, q.tile)


def _gg_operands(seed=21, E=3, C=256, K=256, N=128):
    x = _x(seed, E, C, K, spread=0.5)
    w = _x(seed + 1, E, K, N, spread=0.3) * 0.05
    qx = quantize(x, (1, 1, TILE), tag="t")
    qw = quantize(w, (1, TILE, TILE), tag="t")
    return qx, qw


@pytest.mark.parametrize("skew", ["zero_expert", "all_to_one", "random"])
def test_masked_vs_padded_bitwise(skew):
    """Masked kernels must be BITWISE the padded kernels on the zero-padded
    dispatch layout, under every routing skew (incl. empty experts)."""
    qx, qw = _gg_operands()
    mm = _skew(skew, *qx.data.shape[:2])
    qx = _zero_dead_rows(qx, mm)
    out_m = ops.grouped_gemm_fp8_masked(qx, qw, mm)
    out_p = ops.grouped_gemm_fp8(qx, qw)
    assert np.array_equal(np.asarray(out_m).view(np.uint16),
                          np.asarray(out_p).view(np.uint16))
    q_m = ops.grouped_gemm_fp8_masked_quant_out(qx, qw, mm)
    q_p = ops.grouped_gemm_fp8_quant_out(qx, qw)
    assert np.array_equal(_bits(q_m.data), _bits(q_p.data))
    assert np.array_equal(np.asarray(q_m.scale), np.asarray(q_p.scale))


@pytest.mark.parametrize("skew", ["zero_expert", "all_to_one", "random"])
def test_masked_nt_vs_padded_bitwise(skew):
    """NT (Wgrad) form: masked contraction-tile skip is bitwise-invisible
    when dead token columns are zero."""
    E, M, N, C = 2, 128, 128, 256
    a = _x(23, E, M, C, spread=0.5)
    b = _x(24, E, N, C, spread=0.5) * 0.1
    mm = _skew(skew, E, C, seed=1)
    live = jnp.asarray(np.arange(C)[None, None, :] < np.asarray(mm)[:, None, None])
    qa = quantize(jnp.where(live, a, 0.0), (1, 1, TILE), tag="t")
    qb = quantize(jnp.where(live, b, 0.0), (1, 1, TILE), tag="t")
    out_m = ops.grouped_gemm_nt_fp8_masked(qa, qb, mm)
    out_p = ops.grouped_gemm_nt_fp8(qa, qb)
    assert np.array_equal(np.asarray(out_m).view(np.uint32),
                          np.asarray(out_p).view(np.uint32))


@pytest.mark.parametrize("skew", ["zero_expert", "all_to_one", "random"])
def test_masked_swiglu_epilogue_vs_unfused_pair(skew):
    """The fused SwiGLU+quant GEMM-1 epilogue must be bitwise the unfused
    pipeline (grouped GEMM -> bf16 h -> fused_swiglu_quant kernel)."""
    E, C, K, F = 2, 256, 256, 128
    x = _x(25, E, C, K, spread=0.5)
    w13 = _x(26, E, K, 2 * F, spread=0.3) * 0.05
    qx = quantize(x, (1, 1, TILE), tag="t")
    qw13 = quantize(w13, (1, TILE, TILE), tag="t")
    mm = _skew(skew, E, C, seed=2)
    qx = _zero_dead_rows(qx, mm)
    q_f = ops.grouped_gemm_swiglu_quant_masked(qx, qw13, mm)
    h = ops.grouped_gemm_fp8(qx, qw13)                       # bf16 island
    q_u = ops.fused_swiglu_quant(h.reshape(E * C, 2 * F))
    assert np.array_equal(_bits(q_f.data), _bits(q_u.data.reshape(E, C, F)))
    assert np.array_equal(np.asarray(q_f.scale),
                          np.asarray(q_u.scale.reshape(E, C, F // TILE)))


def test_masked_oracles_tile_granular():
    """Tile-granular mask semantics: with NONZERO payload beyond masked_m,
    dead tiles zero out but partial tiles compute whole — the masked oracles
    encode exactly the kernel behavior."""
    from repro.kernels.grouped_gemm_fp8 import (
        masked_grouped_gemm_fp8_pallas, masked_grouped_gemm_swiglu_quant_pallas)
    from repro.kernels.grouped_gemm_nt_fp8 import masked_grouped_gemm_nt_fp8_pallas
    E, C, K, N = 2, 256, 256, 128
    qx, qw = _gg_operands(seed=31, E=E, C=C, K=K, N=N)
    mm = jnp.asarray([37, 200], jnp.int32)      # mid-tile counts, garbage beyond
    interp = interpret_mode()
    out_m = masked_grouped_gemm_fp8_pallas(qx.data, qx.scale, qw.data,
                                           qw.scale, mm, interpret=interp)
    out_r = ref.masked_grouped_gemm_fp8_ref(qx.data, qx.scale, qw.data,
                                            qw.scale, mm)
    assert np.array_equal(np.asarray(out_m).view(np.uint16),
                          np.asarray(out_r).view(np.uint16))
    d_m, s_m = masked_grouped_gemm_fp8_pallas(qx.data, qx.scale, qw.data,
                                              qw.scale, mm, quant_out=True,
                                              interpret=interp)
    d_r, s_r = ref.masked_grouped_gemm_fp8_quant_out_ref(
        qx.data, qx.scale, qw.data, qw.scale, mm)
    assert np.array_equal(_bits(d_m), _bits(d_r))
    assert np.array_equal(np.asarray(s_m), np.asarray(s_r))

    w13 = _x(33, E, K, 2 * N, spread=0.3) * 0.05
    qw13 = quantize(w13, (1, TILE, TILE), tag="t")
    d_f, s_f = masked_grouped_gemm_swiglu_quant_pallas(
        qx.data, qx.scale, qw13.data, qw13.scale, mm, interpret=interp)
    d_fr, s_fr = ref.masked_grouped_gemm_swiglu_quant_ref(
        qx.data, qx.scale, qw13.data, qw13.scale, mm)
    assert np.array_equal(_bits(d_f), _bits(d_fr))
    assert np.array_equal(np.asarray(s_f), np.asarray(s_fr))

    qa = quantize(_x(34, E, 128, C, spread=0.5), (1, 1, TILE), tag="t")
    qb = quantize(_x(35, E, 128, C, spread=0.5) * 0.1, (1, 1, TILE), tag="t")
    nt_m = masked_grouped_gemm_nt_fp8_pallas(qa.data, qa.scale, qb.data,
                                             qb.scale, mm, interpret=interp)
    nt_r = ref.masked_grouped_gemm_nt_fp8_ref(qa.data, qa.scale, qb.data,
                                              qb.scale, mm)
    assert np.array_equal(np.asarray(nt_m).view(np.uint32),
                          np.asarray(nt_r).view(np.uint32))


def test_capacity_pad_to_block():
    """Regression for the decode-capacity crash: MoE rounds decode capacity
    to 8 but the Pallas grouped GEMMs need 128-row tiles — the ops wrappers
    must pad the capacity axis (payload 0 / scale 1.0) and slice back."""
    qx, qw = _gg_operands(seed=41, E=2, C=8, K=256, N=128)
    out = ops.grouped_gemm_fp8(qx, qw)
    out_r = ref.grouped_gemm_fp8_ref(qx.data, qx.scale, qw.data, qw.scale)
    assert out.shape == (2, 8, 128)
    assert np.array_equal(np.asarray(out).view(np.uint16),
                          np.asarray(out_r).view(np.uint16))
    q_o = ops.grouped_gemm_fp8_quant_out(qx, qw)
    d_r, s_r = ref.grouped_gemm_fp8_quant_out_ref(qx.data, qx.scale,
                                                  qw.data, qw.scale)
    assert np.array_equal(_bits(q_o.data), _bits(d_r))
    mm = jnp.asarray([3, 8], jnp.int32)
    out_m = ops.grouped_gemm_fp8_masked(_zero_dead_rows(qx, mm), qw, mm)
    out_p = ops.grouped_gemm_fp8(_zero_dead_rows(qx, mm), qw)
    assert np.array_equal(np.asarray(out_m).view(np.uint16),
                          np.asarray(out_p).view(np.uint16))


def test_rowwise_wrappers_pad_short_m():
    """quantize_rowwise / fused_swiglu_quant accept M not divisible by the
    128-row kernel block (decode batches)."""
    x = _x(43, 40, 256)
    q = ops.quantize_rowwise(x)
    dr, sr = ref.quantize_rowwise_ref(x)
    assert q.data.shape == (40, 256)
    assert np.array_equal(_bits(q.data), _bits(dr))
    assert np.array_equal(np.asarray(q.scale), np.asarray(sr))
    h = _x(44, 40, 256, spread=0.5).astype(jnp.bfloat16)
    qs = ops.fused_swiglu_quant(h)
    dsr, ssr = ref.fused_swiglu_quant_ref(h)
    assert np.array_equal(_bits(qs.data), _bits(dsr))
    assert np.array_equal(np.asarray(qs.scale), np.asarray(ssr))


def test_quant_out_tiling_asserts_at_trace_time(monkeypatch):
    """The quantizing epilogues expose one scale per (row, BN-tile) as
    (1, TILE) row metadata — valid ONLY while BN == TILE.  A diverged block
    config must fail loudly at trace time, not corrupt scale shapes."""
    import repro.kernels.grouped_gemm_fp8 as gg
    gg._assert_quant_out_tiling()                     # current config: fine
    monkeypatch.setattr(gg, "BN", 2 * TILE)
    with pytest.raises(AssertionError, match="BN == TILE"):
        gg._assert_quant_out_tiling()


def test_ops_wrappers_tile_convention():
    """Every QTensor-producing wrapper follows the normative tile-metadata
    convention: len(tile) == data.ndim, row-tiled = leading 1s + TILE."""
    from repro.core.quant import row_tile
    qx, qw = _gg_operands(seed=45, E=2, C=128, K=256, N=128)
    mm = jnp.asarray([64, 128], jnp.int32)
    w13 = _x(46, 2, 256, 256, spread=0.3) * 0.05
    qw13 = quantize(w13, (1, TILE, TILE), tag="t")
    outs = [
        ops.quantize_rowwise(_x(47, 128, 256)),
        ops.fp8_transpose(ops.quantize_rowwise(_x(48, 128, 256))),
        ops.fused_swiglu_quant(_x(49, 128, 256).astype(jnp.bfloat16)),
        ops.grouped_gemm_fp8_quant_out(qx, qw),
        ops.grouped_gemm_fp8_masked_quant_out(qx, qw, mm),
        ops.grouped_gemm_swiglu_quant_masked(qx, qw13, mm),
    ]
    for q in outs:
        assert len(q.tile) == q.data.ndim, (q.tile, q.data.shape)
        assert q.tile == row_tile(q.data.ndim), (q.tile, q.data.shape)
        assert all(s * t == n for s, t, n in
                   zip(q.scale.shape, q.tile, q.data.shape)), \
            (q.scale.shape, q.tile, q.data.shape)


def test_expert_ffn_masked_matches_padded_fwd_and_grads():
    """End-to-end recipe check: expert_ffn with masked_m (masked kernels on
    every fwd/bwd grouped GEMM) is bitwise the padded path on the dispatch
    layout, outputs AND weight gradients, under skewed routing."""
    from repro.core.linear import expert_ffn
    from repro.core.recipes import get_recipe
    E, C, K, F = 2, 128, 128, 128
    mm = jnp.asarray([48, 128], jnp.int32)
    x = _x(51, E, C, K, spread=0.5)
    qx = _zero_dead_rows(quantize(x, (1, 1, TILE), tag="t"), mm)
    w13 = _x(52, E, K, 2 * F, spread=0.3) * 0.05
    w2 = _x(53, E, F, K, spread=0.3) * 0.05
    # cotangents on dead slots are zero in the real block (p_exp weighting);
    # replicate that with a live-row mask inside the loss
    live = jnp.asarray((np.arange(C)[None, :] < np.asarray(mm)[:, None])
                       ).astype(jnp.float32)[..., None]

    def loss(recipe, masked_m):
        def L(w13, w2):
            y = expert_ffn(recipe, "swiglu", (), (), qx, w13, w2, masked_m)
            return jnp.sum((y.astype(jnp.float32) * live) ** 2)
        return jax.value_and_grad(L, argnums=(0, 1))(w13, w2)

    r_pad = get_recipe("fp8_flow", use_pallas=True)
    r_msk = get_recipe("fp8_flow", use_pallas=True, masked_experts=True)
    y_p, (g13_p, g2_p) = loss(r_pad, None)
    y_m, (g13_m, g2_m) = loss(r_msk, mm)
    assert np.array_equal(np.asarray(y_p), np.asarray(y_m))
    assert np.array_equal(np.asarray(g13_p), np.asarray(g13_m))
    assert np.array_equal(np.asarray(g2_p), np.asarray(g2_m))


def test_xla_path_matches_pallas_path():
    """linear.py's XLA fallbacks must agree with the Pallas kernels (the
    dry-run lowers the XLA path; TPU runs the kernels)."""
    from repro.core.linear import _ggemm, _ggemm_nt, _t_direct
    from repro.core.recipes import get_recipe
    r_x = get_recipe("fp8_flow", use_pallas=False)
    r_p = get_recipe("fp8_flow", use_pallas=True)
    x = _x(13, 2, 128, 256, spread=0.5)
    w = _x(14, 2, 256, 128, spread=0.3) * 0.05
    qx = quantize(x, (1, 1, TILE), tag="t")
    qw = quantize(w, (1, TILE, TILE), tag="t")
    np.testing.assert_allclose(
        np.asarray(_ggemm(r_x, qx, qw), np.float32),
        np.asarray(_ggemm(r_p, qx, qw), np.float32), rtol=2e-2, atol=2e-2)
    ta, tb = _t_direct(r_x, qx), _t_direct(r_p, qx)
    assert np.array_equal(_bits(ta.data), _bits(tb.data))
