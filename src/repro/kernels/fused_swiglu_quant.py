"""Pallas TPU kernel: fused SwiGLU + row-wise FP8 quantization (paper §3.3.2).

Input is the grouped-GEMM-1 output h = [gate | up] (M, 2F) in bf16 (the
paper's deliberate BF16 island).  One kernel pass computes
silu(gate) * up and quantizes it straight to e4m3 + po2 scales — the
activation never round-trips through HBM in bf16, which is the fusion the
paper measures in Fig. 5.

Grid: (M/ROWS, F/TILE).  h is viewed as (M, 2, F) — a zero-copy reshape of
the contiguous [gate | up] layout — so a SINGLE HBM operand (one BlockSpec
fetching a (ROWS, 2, TILE) block) carries both the gate and up tiles of each
step; the compiled kernel declares the operand once instead of streaming the
same buffer through two input declarations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fp8 import E4M3, E4M3_MAX, TILE
from repro.kernels import checked_interpret, out_struct
from repro.kernels.quantize import kernel_po2_scale, put_lane

ROWS = 128


def _swiglu_quant_kernel(h_ref, data_ref, scale_ref):
    g = h_ref[:, 0, :].astype(jnp.float32)
    u = h_ref[:, 1, :].astype(jnp.float32)
    y = (g * jax.lax.logistic(g)) * u                      # SwiGLU, f32
    amax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    s = kernel_po2_scale(amax)
    data_ref[...] = jnp.clip(y / s, -E4M3_MAX, E4M3_MAX).astype(E4M3)
    put_lane(scale_ref, pl.program_id(1), s)


def fused_swiglu_quant_pallas(h: jax.Array, *, interpret: bool):
    """h: (M, 2F) bf16 [gate | up] -> (data (M, F) e4m3, scale (M, F/TILE))."""
    M, twoF = h.shape
    F = twoF // 2
    assert M % ROWS == 0 and F % TILE == 0, (M, F)
    nb_f = F // TILE
    out_shapes = (
        out_struct((M, F), E4M3, h),
        out_struct((M, nb_f), jnp.float32, h),
    )
    return pl.pallas_call(
        _swiglu_quant_kernel,
        grid=(M // ROWS, nb_f),
        in_specs=[
            pl.BlockSpec((ROWS, 2, TILE), lambda i, j: (i, 0, j)),
        ],
        out_specs=(
            pl.BlockSpec((ROWS, TILE), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, nb_f), lambda i, j: (i, 0)),
        ),
        out_shape=out_shapes,
        interpret=checked_interpret(interpret),
    )(h.reshape(M, 2, F))
