"""Recipe-parameterized grouped expert FFN with hand-written VJPs (Fig. 2).

``expert_ffn(recipe, act, x_in, w13, w2)`` computes, per expert e:

    h = x[e] @ w13[e]          (E, C, Fh)     "grouped linear 1"
    a = act(h)                 (E, C, F)      SwiGLU (Fh=2F) or GELU (Fh=F)
    y = a  @ w2[e]             (E, C, D)      "grouped linear 2"

The backward pass is written BY HAND per recipe — this is the paper's whole
point: the recipes differ not in the math but in *where tensors change
format*:

  bf16       pure autodiff, no quantization (0 casts)
  blockwise  TE-style: FP8 only inside the GEMMs, BF16-saved activations,
             fresh column-wise quantizations for Wgrad (8 casts)
  naive_fp8  DeepSeek-style: FP8-saved activations whose Wgrad layouts are
             rebuilt via dequantize->transpose->requantize — the double-
             quantization-error path (10 casts here + 2 at the dispatch
             boundary in moe.py = the paper's 12)
  fp8_flow   this paper: scaling-aware direct transpose for every Wgrad
             layout, fused SwiGLU+quant / dSwiGLU+quant / Dgrad-epilogue
             quant; ONE explicit cast here (the BF16-island gradient
             quantize; the other is the entry quantize at dispatch)

For fp8 recipes the input ``x_in`` is a QTensor and — in fp8_flow — the
returned input-cotangent is ALSO a QTensor (fp8 payload + po2 scales), so the
gradient travels the dispatch all-to-all in FP8, mirroring the forward.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro.core import casts
from repro.core.fp8 import TILE
from repro.core.quant import (QTensor, _dequantize_nocount, dequantize,
                              quantize_blockwise, quantize_rowwise,
                              record_entry_stats, tag_qtensor, tag_saveable)
from repro.core.recipes import Recipe
from repro.core.transpose import transpose_direct, transpose_naive


# ---------------------------------------------------------------------------
# Path selection: Pallas kernels (TPU / interpret) vs pure-XLA equivalents.
# ---------------------------------------------------------------------------
def _ggemm(recipe: Recipe, qx: QTensor, qw: QTensor, out_dtype=jnp.bfloat16,
           masked_m=None):
    """masked_m (int32 (E,), per-expert live rows) routes the Pallas path to
    the masked-layout kernel — bitwise-equal on the zero-padded dispatch
    buffers, so the XLA path may ignore it (padded rows are zero anyway)."""
    if recipe.use_pallas:
        from repro.kernels import ops
        if masked_m is not None:
            return ops.grouped_gemm_fp8_masked(qx, qw,
                                               masked_m).astype(out_dtype)
        return ops.grouped_gemm_fp8(qx, qw).astype(out_dtype)
    # XLA path mirrors the MXU contract: operands dequantized to bf16 (EXACT
    # for e4m3 payloads x po2 scales — bf16 has more mantissa than e4m3) and
    # the dot accumulates in f32.  Halves the materialized operand bytes.
    xf = _dequantize_nocount(qx, jnp.bfloat16)
    wf = _dequantize_nocount(qw, jnp.bfloat16)
    return jnp.matmul(xf, wf,
                      preferred_element_type=jnp.float32).astype(out_dtype)


def _ggemm_nt(recipe: Recipe, qa: QTensor, qb: QTensor, out_dtype=jnp.float32,
              masked_m=None):
    """(E,M,C) x (E,N,C) -> (E,M,N), contraction over last axis of both."""
    if recipe.use_pallas:
        from repro.kernels import ops
        if masked_m is not None:
            return ops.grouped_gemm_nt_fp8_masked(qa, qb,
                                                  masked_m).astype(out_dtype)
        return ops.grouped_gemm_nt_fp8(qa, qb).astype(out_dtype)
    af = _dequantize_nocount(qa, jnp.bfloat16)
    bf = _dequantize_nocount(qb, jnp.bfloat16)
    return jnp.einsum("emc,enc->emn", af, bf,
                      preferred_element_type=jnp.float32).astype(out_dtype)


def _ggemm_quant_out(recipe: Recipe, qx: QTensor, qw: QTensor,
                     masked_m=None) -> QTensor:
    """Grouped GEMM with fused FP8-quantizing epilogue (Dgrad1 path)."""
    casts.record("fused_quantize", "dgrad_epilogue", qx.data.shape[0])
    if recipe.use_pallas:
        from repro.kernels import ops
        if masked_m is not None:
            return ops.grouped_gemm_fp8_masked_quant_out(qx, qw, masked_m)
        return ops.grouped_gemm_fp8_quant_out(qx, qw)
    out = _ggemm(recipe, qx, qw, jnp.bfloat16)
    return quantize_rowwise(out, scale_mode=recipe.scale_mode,
                            tag="dgrad_out", kind="fused_quantize_inner")


def _q_row(recipe: Recipe, x, tag, fused=False) -> QTensor:
    kind = "fused_quantize" if fused else "quantize"
    if recipe.use_pallas and x.ndim == 3:
        from repro.kernels import ops
        casts.record(kind, tag, x.size)
        E, C, K = x.shape
        q = ops.quantize_rowwise(x.reshape(E * C, K))
        return QTensor(q.data.reshape(E, C, K), q.scale.reshape(E, C, K // TILE),
                       (1, 1, TILE))
    return quantize_rowwise(x, scale_mode=recipe.scale_mode, tag=tag, kind=kind)


def _t_direct(recipe: Recipe, q: QTensor) -> QTensor:
    """Scaling-aware direct transpose of the last two axes (casting-free)."""
    if recipe.use_pallas:
        from repro.kernels import interpret_mode
        from repro.kernels.fp8_transpose import fp8_transpose_pallas
        E, M, K = q.shape
        interpret = interpret_mode()
        dt, st = jax.vmap(lambda d, s: fp8_transpose_pallas(
            d, s, interpret=interpret))(
            q.data, q.scale.reshape(E, M, K // TILE))
        return QTensor(dt, st, (1, 1, TILE))
    return transpose_direct(q)


def _t_naive(recipe: Recipe, q: QTensor) -> QTensor:
    """Dequantize -> transpose -> requantize (2 explicit casts)."""
    return transpose_naive(q, scale_mode=recipe.scale_mode)


def _block_t(qw: QTensor) -> QTensor:
    """Transpose a (TILE,TILE)-block-quantized weight — exact relabeling."""
    return QTensor(jnp.swapaxes(qw.data, -1, -2),
                   jnp.swapaxes(qw.scale, -1, -2), qw.tile)


def _fused_swiglu_quant(recipe: Recipe, h) -> QTensor:
    casts.record("fused_quantize", "swiglu_quant", h.size)
    if recipe.use_pallas:
        from repro.kernels import ops
        E, C, Fh = h.shape
        q = ops.fused_swiglu_quant(h.reshape(E * C, Fh))
        F = Fh // 2
        return QTensor(q.data.reshape(E, C, F), q.scale.reshape(E, C, F // TILE),
                       (1, 1, TILE))
    a = _swiglu(h)
    return quantize_rowwise(a, scale_mode=recipe.scale_mode,
                            tag="swiglu_quant", kind="fused_quantize_inner")


# ---------------------------------------------------------------------------
# Activations (computed in f32, the BF16 island of §3.2).
# ---------------------------------------------------------------------------
def _swiglu(h):
    g, u = jnp.split(h.astype(jnp.float32), 2, axis=-1)
    return (g * jax.lax.logistic(g) * u).astype(jnp.bfloat16)


def _dswiglu(h, ga):
    g, u = jnp.split(h.astype(jnp.float32), 2, axis=-1)
    ga = ga.astype(jnp.float32)
    s = jax.lax.logistic(g)
    silu = g * s
    dgate = ga * u * (s + silu * (1.0 - s))
    dup = ga * silu
    return jnp.concatenate([dgate, dup], axis=-1).astype(jnp.bfloat16)


def _geglu(h):
    g, u = jnp.split(h.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.gelu(g, approximate=True) * u).astype(jnp.bfloat16)


def _dgeglu(h, ga):
    g, u = jnp.split(h.astype(jnp.float32), 2, axis=-1)
    ga = ga.astype(jnp.float32)
    _, vjp = jax.vjp(lambda t: jax.nn.gelu(t, approximate=True), g)
    dgate = vjp(ga * u)[0]
    dup = ga * jax.nn.gelu(g, approximate=True)
    return jnp.concatenate([dgate, dup], axis=-1).astype(jnp.bfloat16)


def _gelu(h):
    return jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(jnp.bfloat16)


def _dgelu(h, ga):
    h32 = h.astype(jnp.float32)
    _, vjp = jax.vjp(lambda t: jax.nn.gelu(t, approximate=True), h32)
    return vjp(ga.astype(jnp.float32))[0].astype(jnp.bfloat16)


def _relu(h):
    return jax.nn.relu(h.astype(jnp.float32)).astype(jnp.bfloat16)


def _drelu(h, ga):
    return jnp.where(h.astype(jnp.float32) > 0,
                     ga.astype(jnp.float32), 0.0).astype(jnp.bfloat16)


_ACT_FWD = {"swiglu": _swiglu, "geglu": _geglu, "gelu": _gelu, "relu": _relu}
_ACT_BWD = {"swiglu": _dswiglu, "geglu": _dgeglu, "gelu": _dgelu,
            "relu": _drelu}


def _act_fwd(act: str, h):
    return _ACT_FWD[act](h)


def _act_bwd(act: str, h, ga):
    return _ACT_BWD[act](h, ga)


# ---------------------------------------------------------------------------
# The recipe-dispatched expert FFN.
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def expert_ffn(recipe: Recipe, act: str, wg_axes: tuple, gx_axes: tuple,
               x_in, w13, w2, masked_m=None):
    """wg_axes: mesh axes to psum weight-gradients over (the DP reduction —
    tokens are sharded over them while weights are replicated).  gx_axes:
    axes to psum the input-gradient over (TP-sharded expert case).  Both are
    () outside shard_map.  masked_m: optional per-expert live-row counts
    (int32 (E,)) — routes the fp8_flow Pallas grouped GEMMs (fwd AND every
    backward Dgrad/Wgrad) through the masked layout; other recipes ignore
    it."""
    y, _ = _ffn_fwd(recipe, act, wg_axes, gx_axes, x_in, w13, w2, masked_m)
    return y


def _quant_weights(recipe: Recipe, w13, w2):
    # W8-resident serving: weights may arrive pre-quantized (serve/w8.py)
    qw13 = w13 if isinstance(w13, QTensor) else quantize_blockwise(
        w13, scale_mode=recipe.scale_mode, tag="q_w13")
    qw2 = w2 if isinstance(w2, QTensor) else quantize_blockwise(
        w2, scale_mode=recipe.scale_mode, tag="q_w2")
    return qw13, qw2


def _ffn_fwd(recipe: Recipe, act: str, wg_axes: tuple, gx_axes: tuple,
             x_in, w13, w2, masked_m=None):
    name = recipe.name
    if name == "bf16":
        x = x_in
        h = tag_saveable(jnp.matmul(x.astype(jnp.bfloat16),
                                    w13.astype(jnp.bfloat16)), "stage_ffn_h")
        a = _act_fwd(act, h)
        y = jnp.matmul(a, w2.astype(jnp.bfloat16))
        return y, (x, h, w13, w2)

    qw13, qw2 = _quant_weights(recipe, w13, w2)

    if name == "fp8_flow":
        y, (qx, qa, h_saved) = ffn_fwd_fp8_core(recipe, act, x_in, qw13, qw2,
                                                masked_m=masked_m)
        wit = (jnp.zeros((0,), w13.dtype), jnp.zeros((0,), w2.dtype))
        return y, (qx, qa, h_saved, qw13, qw2, wit, masked_m)

    if name == "naive_fp8":
        # x arrives in BF16 (the dispatch DQ'd it — Fig 2c's Q/DQ-around-comm)
        x = x_in
        qx = tag_qtensor(_q_row(recipe, x, "q_gemm1_in"), "fp8_qx")  # (3)
        h = _ggemm(recipe, qx, qw13, jnp.bfloat16)
        a = _act_fwd(act, h)                                 # separate kernel
        qa = tag_qtensor(_q_row(recipe, a, "q_gemm2_in"), "fp8_qa")  # (4)
        y = _ggemm(recipe, qa, qw2, jnp.bfloat16)
        # x and a are SAVED IN FP8 (DeepSeek's memory trick) — their Wgrad
        # layouts in bwd must go through dequant->transpose->requant.
        wit = (jnp.zeros((0,), w13.dtype), jnp.zeros((0,), w2.dtype))
        return y, (qx, qa, qw13, qw2, wit)

    if name == "blockwise":
        x = x_in                                             # bf16
        qx = _q_row(recipe, x, "q_gemm1_in")                 # explicit cast
        h = tag_saveable(_ggemm(recipe, qx, qw13, jnp.bfloat16),
                         "stage_ffn_h")
        a = _act_fwd(act, h)
        qa = _q_row(recipe, a, "q_gemm2_in")                 # explicit cast
        y = _ggemm(recipe, qa, qw2, jnp.bfloat16)
        wit = (jnp.zeros((0,), w13.dtype), jnp.zeros((0,), w2.dtype))
        return y, (x, h, qw13, qw2, wit)

    raise ValueError(name)


def _psum(v, axes):
    return jax.lax.psum(v, axes) if axes else v


# ---------------------------------------------------------------------------
# fp8_flow FFN core (shared by expert_ffn's VJP and the overlapped dispatch
# pipeline in core/moe.py, which hand-writes its backward so the one explicit
# island quantize can be hoisted OUT of the per-chunk loop).
# ---------------------------------------------------------------------------
def _use_swiglu_epilogue(recipe: Recipe, act: str, masked_m) -> bool:
    """The fused SwiGLU+quant GEMM-1 epilogue applies on the masked Pallas
    path only, and only when h need not be materialized for saving."""
    return (recipe.swiglu_epilogue and act == "swiglu" and recipe.use_pallas
            and masked_m is not None and not recipe.save_h)


def ffn_fwd_fp8_core(recipe: Recipe, act: str, qx: QTensor, qw13: QTensor,
                     qw2: QTensor, masked_m=None):
    """fp8_flow grouped FFN forward on an already-quantized input.
    Returns (y bf16, (qx, qa, h_saved)) — the residuals the backward core
    needs (the weights the caller already holds).  qx/qa come back
    checkpoint_name-tagged ('fp8_qx'/'fp8_qa'): callers must save THESE so
    the MemoryPlan 'fp8_resident' policy (train/memory.py) keeps the
    QTensor stage outputs resident across the forward/backward boundary."""
    qx = tag_qtensor(qx, "fp8_qx")
    if _use_swiglu_epilogue(recipe, act, masked_m):
        # GEMM-1 with the SwiGLU + re-quantize fused into its last K-step:
        # the BF16 island lives only in VMEM (bitwise the unfused pair).
        from repro.kernels import ops
        # same ledger entry as the unfused kernel pair (h.size = E*C*2F)
        casts.record("fused_quantize", "swiglu_quant",
                     qx.data.shape[0] * qx.data.shape[1] * qw13.data.shape[-1])
        qa = ops.grouped_gemm_swiglu_quant_masked(qx, qw13, masked_m)
        qa = tag_qtensor(qa, "fp8_qa")
        y = _ggemm(recipe, qa, qw2, jnp.bfloat16, masked_m=masked_m)
        return y, (qx, qa, None)
    h = _ggemm(recipe, qx, qw13, jnp.bfloat16,
               masked_m=masked_m)                           # BF16 island in
    h = tag_saveable(h, "stage_ffn_h")
    if act == "swiglu":
        qa = _fused_swiglu_quant(recipe, h)
    else:
        # fused <act>+quant: same one-pass contract as the SwiGLU kernel
        casts.record("fused_quantize", "act_quant", h.size)
        qa = quantize_rowwise(_act_fwd(act, h), scale_mode=recipe.scale_mode,
                              tag="act_quant", kind="fused_quantize_inner")
    qa = tag_qtensor(qa, "fp8_qa")
    y = _ggemm(recipe, qa, qw2, jnp.bfloat16, masked_m=masked_m)
    return y, (qx, qa, h if recipe.save_h else None)


def ffn_bwd_fp8_core(recipe: Recipe, act: str, gx_axes: tuple, qx: QTensor,
                     qa: QTensor, h_saved, qw13: QTensor, qw2: QTensor,
                     qg: QTensor, masked_m=None):
    """fp8_flow grouped FFN backward given an ALREADY-QUANTIZED output
    cotangent ``qg`` — the explicit BF16-island quantize happens in the
    caller (once per step, even when the FFN itself runs per micro-chunk).
    Returns (gx QTensor, wg13 f32, wg2 f32): the input-gradient is FP8 on
    both branches (fused Dgrad1 epilogue, or post-psum quantize when
    gx_axes); weight grads are UNREDUCED (the caller psums over its DP
    axes).  masked_m skips dead capacity tiles in all five grouped GEMMs
    (Dgrad rows beyond the count are zero because the combine's p_exp
    weighting zeros dead slots upstream; NT forms skip zero token
    columns)."""
    # Dgrad2: FP8 x FP8, block-transposed weight (exact relabeling)
    ga = _ggemm(recipe, qg, _block_t(qw2), jnp.bfloat16, masked_m=masked_m)
    # Wgrad2 via scaling-aware DIRECT transposes — zero casts
    wg2 = _ggemm_nt(recipe, _t_direct(recipe, qa), _t_direct(recipe, qg),
                    masked_m=masked_m)
    # BF16 island: recompute h (FP8 activation checkpointing) or reuse
    h = h_saved if h_saved is not None else _ggemm(recipe, qx, qw13,
                                                   jnp.bfloat16,
                                                   masked_m=masked_m)
    gh = _act_bwd(act, h, ga)
    casts.record("fused_quantize", "dact_quant", gh.size)
    qgh = quantize_rowwise(gh, scale_mode=recipe.scale_mode,
                           tag="dact_quant", kind="fused_quantize_inner")
    if gx_axes:
        # TP-sharded experts: the input-gradient partial-sums over the
        # F-shards first; the fused quantizing epilogue runs after the
        # psum (a reduction — kept out of FP8 by design).
        gx_f32 = _ggemm(recipe, qgh, _block_t(qw13), jnp.float32,
                        masked_m=masked_m)
        casts.record("fused_quantize", "dgrad_epilogue", gx_f32.size)
        gx = quantize_rowwise(_psum(gx_f32, gx_axes),
                              scale_mode=recipe.scale_mode,
                              tag="dgrad_out", kind="fused_quantize_inner")
    else:
        # Dgrad1 with fused quantizing epilogue -> FP8 input-gradient
        gx = _ggemm_quant_out(recipe, qgh, _block_t(qw13), masked_m=masked_m)
    # Wgrad1, again via direct transposes
    wg13 = _ggemm_nt(recipe, _t_direct(recipe, qx), _t_direct(recipe, qgh),
                     masked_m=masked_m)
    return gx, wg13, wg2


def _ffn_bwd(recipe: Recipe, act: str, wg_axes: tuple, gx_axes: tuple,
             res, gy):
    name = recipe.name
    gy = gy.astype(jnp.bfloat16)

    if name == "bf16":
        x, h, w13, w2 = res
        a = _act_fwd(act, h)
        ga = jnp.matmul(gy, jnp.swapaxes(w2.astype(jnp.bfloat16), -1, -2))
        wg2 = jnp.einsum("ecf,ecd->efd", a.astype(jnp.float32),
                         gy.astype(jnp.float32))
        gh = _act_bwd(act, h, ga)
        gx = jnp.matmul(gh, jnp.swapaxes(w13.astype(jnp.bfloat16), -1, -2))
        wg13 = jnp.einsum("eck,ecf->ekf", x.astype(jnp.float32),
                          gh.astype(jnp.float32))
        return (_psum(gx, gx_axes), _psum(wg13, wg_axes).astype(w13.dtype),
                _psum(wg2, wg_axes).astype(w2.dtype), None)

    if name == "fp8_flow":
        qx, qa, h_saved, qw13, qw2, (wit13, wit2), masked_m = res
        w13_dt, w2_dt = wit13.dtype, wit2.dtype
        # ---- the single explicit backward cast: BF16 island -> FP8 ----
        qg = _q_row(recipe, gy, "q_bwd_island")
        gx_q, wg13, wg2 = ffn_bwd_fp8_core(recipe, act, gx_axes, qx, qa,
                                           h_saved, qw13, qw2, qg,
                                           masked_m=masked_m)
        return (gx_q, _psum(wg13, wg_axes).astype(w13_dt),
                _psum(wg2, wg_axes).astype(w2_dt), None)

    if name == "naive_fp8":
        qx, qa, qw13, qw2, (wit13, wit2) = res
        w13_dt, w2_dt = wit13.dtype, wit2.dtype
        qg = _q_row(recipe, gy, "q_bwd_dgrad2")              # explicit (5)
        ga = _ggemm(recipe, qg, _block_t(qw2), jnp.bfloat16)
        # Wgrad column layouts for the FP8-SAVED activations must be rebuilt
        # via dequantize->transpose->requantize — the double-quantization-
        # error path (2 explicit casts each: 6,7 / 10,11); the BF16-live
        # gradients are freshly column-quantized (8 / 12).
        qaT = _t_naive(recipe, qa)                           # dq+q (6,7)
        qgT = _q_row(recipe, jnp.swapaxes(gy, -1, -2), "q_bwd_wgrad2_g")  # (8)
        wg2 = _ggemm_nt(recipe, qaT, qgT)
        h = _ggemm(recipe, qx, qw13, jnp.bfloat16)
        gh = _act_bwd(act, h, ga)
        qgh = _q_row(recipe, gh, "q_bwd_dgrad1")             # explicit (9)
        gx = _ggemm(recipe, qgh, _block_t(qw13), jnp.bfloat16)  # bf16 combine
        qxT = _t_naive(recipe, qx)                           # dq+q (10,11)
        qghT = _q_row(recipe, jnp.swapaxes(gh, -1, -2), "q_bwd_wgrad1_g")  # (12)
        wg13 = _ggemm_nt(recipe, qxT, qghT)
        return (_psum(gx, gx_axes), _psum(wg13, wg_axes).astype(w13_dt),
                _psum(wg2, wg_axes).astype(w2_dt), None)

    if name == "blockwise":
        x, h, qw13, qw2, (wit13, wit2) = res
        w13_dt, w2_dt = wit13.dtype, wit2.dtype
        qg = _q_row(recipe, gy, "q_bwd_dgrad2")              # explicit
        ga = _ggemm(recipe, qg, _block_t(qw2), jnp.bfloat16)
        a = _act_fwd(act, h)
        # fresh column-wise quantizations from the BF16-saved tensors
        qaT = _q_row(recipe, jnp.swapaxes(a, -1, -2), "q_bwd_wgrad2_a")
        qgT = _q_row(recipe, jnp.swapaxes(gy, -1, -2), "q_bwd_wgrad2_g")
        wg2 = _ggemm_nt(recipe, qaT, qgT)
        gh = _act_bwd(act, h, ga)
        qgh = _q_row(recipe, gh, "q_bwd_dgrad1")             # explicit
        gx = _ggemm(recipe, qgh, _block_t(qw13), jnp.bfloat16)
        qghT = _q_row(recipe, jnp.swapaxes(gh, -1, -2), "q_bwd_wgrad1_g")
        qxT = _q_row(recipe, jnp.swapaxes(x, -1, -2), "q_bwd_wgrad1_x")
        wg13 = _ggemm_nt(recipe, qxT, qghT)
        return (_psum(gx, gx_axes), _psum(wg13, wg_axes).astype(w13_dt),
                _psum(wg2, wg_axes).astype(w2_dt), None)

    raise ValueError(name)


expert_ffn.defvjp(_ffn_fwd, _ffn_bwd)


# ---------------------------------------------------------------------------
# Entry/exit bridges between the BF16 residual stream and the FP8 pathway.
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(0,))
def quantize_entry(recipe: Recipe, x) -> QTensor:
    """The paper's 'entry point' cast (explicit, counted).  Backward: the
    FP8 input-gradient QTensor is dequantized INSIDE the consuming add
    (fused), closing the FP8 loop."""
    return quantize_rowwise(x, scale_mode=recipe.scale_mode, tag="q_entry")


def _qe_fwd(recipe, x):
    return quantize_entry(recipe, x), jnp.zeros((0,), x.dtype)


def _qe_bwd(recipe, wit, qg: QTensor):
    casts.record("fused_dequantize", "entry_bwd", qg.data.size)
    return (_dequantize_nocount(qg, wit.dtype),)


quantize_entry.defvjp(_qe_fwd, _qe_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def dequantize_exit(recipe: Recipe, q: QTensor):
    """naive_fp8's post-dispatch DQ (explicit) paired with an explicit bwd
    quantize — the Q/DQ-around-comm cost of Table 1."""
    return dequantize(q, jnp.bfloat16, tag="dq_post_dispatch")


def _de_fwd(recipe, q):
    return dequantize_exit(recipe, q), (q.scale.shape, q.tile)


def _de_bwd(recipe, res, g):
    scale_shape, tile = res
    qg = quantize_rowwise(g, scale_mode=recipe.scale_mode, tag="q_bwd_dispatch")
    return (qg,)


dequantize_exit.defvjp(_de_fwd, _de_bwd)


def dense_mlp(recipe: Recipe, act: str, x, w13, w2):
    """Dense-arch specialization: the FP8-centric MLP (no dispatch).

    x: (T, D); w13: (D, Fh); w2: (F, D).  T, D, F must be 128-multiples."""
    T, D = x.shape
    Tp = (T + 127) // 128 * 128
    Dp = (D + 127) // 128 * 128
    if Tp != T or Dp != D:
        # zero-pad to the 128-tile alignment the FP8 pathway needs; zero
        # rows/cols contribute nothing to outputs or gradients
        x = jnp.pad(x, ((0, Tp - T), (0, Dp - D)))
        w13 = jnp.pad(w13, ((0, Dp - D), (0, 0)))
        w2 = jnp.pad(w2, ((0, 0), (0, Dp - D)))
    x3 = x.reshape(1, Tp, Dp)
    w13_3, w2_3 = w13[None], w2[None]
    if recipe.name in ("bf16", "blockwise", "naive_fp8"):
        # blockwise/naive quantize inside the FFN (per-GEMM Q); no dispatch
        # boundary exists for a dense MLP.
        return expert_ffn(recipe, act, (), (), x3.astype(jnp.bfloat16)
                          if recipe.name != "bf16" else x3,
                          w13_3, w2_3)[0][:T, :D]
    # fp8_flow: quantize once at entry, FP8-native pathway end to end
    qx = quantize_entry(recipe, x3)
    record_entry_stats("q_entry_mlp", x3, qx)
    y = expert_ffn(recipe, act, (), (), qx, w13_3, w2_3)
    return y[0][:T, :D]
