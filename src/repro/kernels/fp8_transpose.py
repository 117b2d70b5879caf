"""Pallas TPU kernel: scaling-aware FP8 direct transpose (paper Algorithm 1).

Grid: (K/128, M/128) — one 128x128 e4m3 block per step, resident in VMEM.
Per block:
  s_max  = max of the 128 row scales covering this block
  k_i    = log2(s_max / s_i)            (integer: scales are powers of two)
  out    = block^T with each element's exponent reduced by k_i, including
           correct round-to-nearest-even shifts into the subnormal range —
           pure integer ops on the bitcast uint8 encodings, no float math on
           the payload.  This is the TPU analogue of the paper's CUDA
           exponent-manipulation kernel: one VMEM round trip, VPU-only.

Encodings (e4m3fn): value = (-1)^s * 2^(E-7) * (1+M/8) for E>=1,
                    value = (-1)^s * 2^-6 * (M/8)      for E==0 (subnormal).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fp8 import BLOCK, E4M3
from repro.kernels import checked_interpret, out_struct
from repro.kernels.quantize import put_lane, take_index

_SIGN_MASK = 0x80
_EXP_SHIFT = 3
_EXP_MASK = 0xF
_MAN_MASK = 0x7


def _rshift_rne(v: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Round-to-nearest-even right shift of a non-negative int32 array."""
    n = jnp.clip(n, 0, 15)
    floor = jnp.right_shift(v, n)
    rem = v - jnp.left_shift(floor, n)
    half = jnp.left_shift(jnp.int32(1), jnp.maximum(n - 1, 0))
    round_up = (n > 0) & ((rem > half) | ((rem == half) & ((floor & 1) == 1)))
    return floor + jnp.where(round_up, 1, 0)


def _rebase_exponent(enc_u8: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Divide encoded e4m3 values by 2^k (k >= 0 int32), re-encoding exactly."""
    enc = enc_u8.astype(jnp.int32)
    sign = enc & _SIGN_MASK
    e = jnp.right_shift(enc, _EXP_SHIFT) & _EXP_MASK
    m = enc & _MAN_MASK

    # normal input, stays normal: E' = E - k  (requires E - k >= 1)
    e_new = e - k
    normal_out = sign | jnp.left_shift(e_new & _EXP_MASK, _EXP_SHIFT) | m

    # normal input, falls into subnormal: shift (8+M) right by (1 - (E-k)), RNE
    shift = 1 - e_new
    m_sub = _rshift_rne(8 + m, shift)
    # a carry to 8 means it rounded up to the minimum normal (E'=1, M'=0)
    sub_from_normal = jnp.where(m_sub >= 8,
                                sign | (1 << _EXP_SHIFT),
                                sign | m_sub)

    # subnormal input: M' = rne(M >> k), stays subnormal
    sub_from_sub = sign | _rshift_rne(m, k)

    out = jnp.where(e == 0, sub_from_sub,
                    jnp.where(e_new >= 1, normal_out, sub_from_normal))
    return out.astype(jnp.uint8)


def _f32_exponent(s):
    """Biased exponent field of a positive f32 (exact for po2 scales)."""
    return jnp.right_shift(jax.lax.bitcast_convert_type(s, jnp.int32), 23)


def _transpose_kernel(x_ref, s_ref, xo_ref, so_ref):
    """x_ref: (BLOCK, BLOCK) e4m3; s_ref: (BLOCK, nb_k) f32 row scales of
    the block row; so_ref: (BLOCK, nb_m) scales of the output block row."""
    j, i = pl.program_id(0), pl.program_id(1)
    s = take_index(s_ref[...], j, 1)                   # (BLOCK, 1) po2
    s_max = jnp.max(s, axis=0, keepdims=True)          # (1, 1)
    # k = log2(s_max / s): scales are powers of two, so it is the exponent
    # difference
    k = _f32_exponent(s_max) - _f32_exponent(s)        # (BLOCK, 1), >= 0

    enc = jax.lax.bitcast_convert_type(x_ref[...], jnp.uint8)
    rebased = _rebase_exponent(enc, k)                 # rows rebased onto s_max
    out = jax.lax.bitcast_convert_type(rebased, E4M3).T
    xo_ref[...] = out
    put_lane(so_ref, i, jnp.broadcast_to(s_max, (BLOCK, 1)))


def fp8_transpose_pallas(data: jax.Array, scale: jax.Array, *,
                         interpret: bool):
    """data: (M, K) e4m3 row-wise; scale: (M, K/BLOCK) f32 po2.

    Returns (data_t: (K, M) e4m3, scale_t: (K, M/BLOCK) f32) with the
    transposed tensor quantized at block-aligned scales.  The grid walks M
    fastest so each output block row's scale block stays resident.
    """
    M, K = data.shape
    assert M % BLOCK == 0 and K % BLOCK == 0, (M, K)
    nb_m, nb_k = M // BLOCK, K // BLOCK

    out_shapes = (
        out_struct((K, M), data.dtype, data, scale),
        out_struct((K, nb_m), jnp.float32, data, scale),
    )
    return pl.pallas_call(
        _transpose_kernel,
        grid=(nb_k, nb_m),
        in_specs=[
            pl.BlockSpec((BLOCK, BLOCK), lambda j, i: (i, j)),
            pl.BlockSpec((BLOCK, nb_k), lambda j, i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((BLOCK, BLOCK), lambda j, i: (j, i)),
            pl.BlockSpec((BLOCK, nb_m), lambda j, i: (j, 0)),
        ),
        out_shape=out_shapes,
        interpret=checked_interpret(interpret),
    )(data, scale)
