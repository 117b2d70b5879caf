"""Jit'd public wrappers for the Pallas kernel suite.

On the CPU backend the kernels execute in interpret mode (the kernel body
runs in Python, validating TPU semantics); on a TPU they are compiled to
Mosaic (``repro.kernels.interpret_mode``).  Every wrapper has a matching
pure-jnp oracle in ``ref.py``.

Alignment contract: the kernels require 128-aligned blocks, but MoE capacity
is only rounded to 8 on the decode path (``moe._round_up(..., 8)``), so the
row/capacity axes here are PADDED to the kernel block (payload 0, scale 1.0
— the bits quantizing a zero row produces) and outputs sliced back.  Model
axes (K, N, F) are true 128 multiples everywhere in the repo and stay
asserted.  The NT wrappers keep the hard assert on the contraction axis: a
row-tiled QTensor over that axis cannot even be constructed unless it is a
TILE multiple.

Masked variants take the per-expert live-row counts ``masked_m`` (int32
(E,)) from the dispatch plan plus a STATIC ``expected_m`` tuning hint; when
``expected_m >= capacity`` the wrapper falls back to the padded kernel
(masking would only add scalar-prefetch overhead at full load).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant import QTensor, row_tile
from repro.core.fp8 import TILE
from repro.kernels import interpret_mode
from repro.kernels.fp8_transpose import fp8_transpose_pallas
from repro.kernels.fused_permute_pad import fused_permute_pad_pallas
from repro.kernels.fused_swiglu_quant import fused_swiglu_quant_pallas
from repro.kernels.grouped_gemm_fp8 import (
    BM,
    grouped_gemm_fp8_pallas,
    masked_grouped_gemm_fp8_pallas,
    masked_grouped_gemm_swiglu_quant_pallas,
)
from repro.kernels.grouped_gemm_nt_fp8 import (
    grouped_gemm_nt_fp8_pallas,
    masked_grouped_gemm_nt_fp8_pallas,
)
from repro.kernels.quantize import ROWS, quantize_rowwise_pallas


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_rows(x: jax.Array, axis: int, n_to: int, value=0):
    """Zero-pad (or 1.0-pad, for scales) one axis up to n_to rows."""
    n = x.shape[axis]
    if n == n_to:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n_to - n)
    return jnp.pad(x, pad, constant_values=value)


def _pad_q_axis(q: QTensor, axis: int, block: int) -> QTensor:
    """Pad a QTensor's element-granular axis (tile[axis] == 1) to a block
    multiple: payload 0, scale 1.0 — exactly what quantizing a zero row
    emits, so padded rows are bitwise-inert through every kernel."""
    n = q.data.shape[axis]
    n_to = _round_up(n, block)
    if n_to == n:
        return q
    assert q.tile[axis] == 1, (q.tile, axis)
    return QTensor(_pad_rows(q.data, axis, n_to),
                   _pad_rows(q.scale, axis, n_to, value=1.0), q.tile)


@jax.jit
def quantize_rowwise(x: jax.Array) -> QTensor:
    M = x.shape[0]
    data, scale = quantize_rowwise_pallas(
        _pad_rows(x, 0, _round_up(M, ROWS)), interpret=interpret_mode())
    return QTensor(data=data[:M], scale=scale[:M], tile=row_tile(2))


@jax.jit
def fp8_transpose(q: QTensor) -> QTensor:
    data, scale = fp8_transpose_pallas(q.data, q.scale,
                                       interpret=interpret_mode())
    return QTensor(data=data, scale=scale, tile=row_tile(2))


@jax.jit
def fused_swiglu_quant(h: jax.Array) -> QTensor:
    M = h.shape[0]
    data, scale = fused_swiglu_quant_pallas(
        _pad_rows(h, 0, _round_up(M, ROWS)), interpret=interpret_mode())
    return QTensor(data=data[:M], scale=scale[:M], tile=row_tile(2))


@functools.partial(jax.jit, static_argnames=("n_out",))
def fused_permute_pad(q: QTensor, row_map: jax.Array, n_out: int) -> QTensor:
    data, scale = fused_permute_pad_pallas(q.data, q.scale, row_map, n_out,
                                           interpret=interpret_mode())
    return QTensor(data=data, scale=scale, tile=row_tile(2))


@jax.jit
def grouped_gemm_fp8(qx: QTensor, qw: QTensor):
    """qx: (E, C, K) row-wise; qw: (E, K, N) block-wise -> (E, C, N) bf16."""
    C = qx.data.shape[1]
    qx = _pad_q_axis(qx, 1, BM)
    out = grouped_gemm_fp8_pallas(qx.data, qx.scale, qw.data, qw.scale,
                                  interpret=interpret_mode())
    return out[:, :C]


@jax.jit
def grouped_gemm_fp8_quant_out(qx: QTensor, qw: QTensor) -> QTensor:
    """Grouped GEMM whose epilogue quantizes straight to e4m3 (Dgrad path)."""
    C = qx.data.shape[1]
    qx = _pad_q_axis(qx, 1, BM)
    data, scale = grouped_gemm_fp8_pallas(qx.data, qx.scale, qw.data, qw.scale,
                                          quant_out=True,
                                          interpret=interpret_mode())
    return QTensor(data=data[:, :C], scale=scale[:, :C], tile=row_tile(3))


@jax.jit
def grouped_gemm_nt_fp8(qa: QTensor, qb: QTensor):
    """qa: (E, M, C), qb: (E, N, C) both row-wise over C -> (E, M, N) f32."""
    return grouped_gemm_nt_fp8_pallas(qa.data, qa.scale, qb.data, qb.scale,
                                      interpret=interpret_mode())


# ---------------------------------------------------------------------------
# Masked layout entry points.
# ---------------------------------------------------------------------------
def _use_padded(expected_m, C: int) -> bool:
    return expected_m is not None and expected_m >= C


@functools.partial(jax.jit, static_argnames=("expected_m",))
def grouped_gemm_fp8_masked(qx: QTensor, qw: QTensor, masked_m: jax.Array,
                            expected_m: int | None = None):
    """Masked grouped GEMM: capacity tiles beyond masked_m[e] skip the MXU."""
    C = qx.data.shape[1]
    if _use_padded(expected_m, C):
        return grouped_gemm_fp8(qx, qw)
    qx = _pad_q_axis(qx, 1, BM)
    out = masked_grouped_gemm_fp8_pallas(
        qx.data, qx.scale, qw.data, qw.scale, masked_m.astype(jnp.int32),
        interpret=interpret_mode())
    return out[:, :C]


@functools.partial(jax.jit, static_argnames=("expected_m",))
def grouped_gemm_fp8_masked_quant_out(qx: QTensor, qw: QTensor,
                                      masked_m: jax.Array,
                                      expected_m: int | None = None) -> QTensor:
    C = qx.data.shape[1]
    if _use_padded(expected_m, C):
        return grouped_gemm_fp8_quant_out(qx, qw)
    qx = _pad_q_axis(qx, 1, BM)
    data, scale = masked_grouped_gemm_fp8_pallas(
        qx.data, qx.scale, qw.data, qw.scale, masked_m.astype(jnp.int32),
        quant_out=True, interpret=interpret_mode())
    return QTensor(data=data[:, :C], scale=scale[:, :C], tile=row_tile(3))


@functools.partial(jax.jit, static_argnames=("expected_m",))
def grouped_gemm_nt_fp8_masked(qa: QTensor, qb: QTensor, masked_m: jax.Array,
                               expected_m: int | None = None):
    """Masked NT (Wgrad) form: contraction (token) tiles beyond masked_m[e]
    are skipped — bitwise-invisible because dead token columns are zero."""
    C = qa.data.shape[2]
    if _use_padded(expected_m, C):
        return grouped_gemm_nt_fp8(qa, qb)
    return masked_grouped_gemm_nt_fp8_pallas(
        qa.data, qa.scale, qb.data, qb.scale, masked_m.astype(jnp.int32),
        interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("expected_m",))
def grouped_gemm_swiglu_quant_masked(qx: QTensor, qw13: QTensor,
                                     masked_m: jax.Array,
                                     expected_m: int | None = None) -> QTensor:
    """Masked grouped GEMM-1 with the fused SwiGLU + e4m3 re-quantize
    epilogue: (E, C, K) x (E, K, 2F) -> QTensor (E, C, F) row-tiled.  The
    bf16 island h never reaches HBM.  ``expected_m >= C`` does NOT fall back
    (the fusion is worth it at any load); masked_m = full C gives the padded
    bits anyway."""
    C = qx.data.shape[1]
    qx = _pad_q_axis(qx, 1, BM)
    data, scale = masked_grouped_gemm_swiglu_quant_pallas(
        qx.data, qx.scale, qw13.data, qw13.scale, masked_m.astype(jnp.int32),
        interpret=interpret_mode())
    return QTensor(data=data[:, :C], scale=scale[:, :C], tile=row_tile(3))
