"""Pallas TPU kernel: row-wise per-128-tile FP8 quantization (po2 scales).

Grid: (M/ROWS, K/TILE).  Each step loads a (ROWS, TILE) bf16/f32 block into
VMEM, computes the per-row po2 scale for that 128-wide tile, and writes the
e4m3 payload + the scale column.  One HBM read + two writes; the amax
reduction and the exponent ceil run on the VPU.

Scale layout: a TPU block's last dim must be a 128-multiple or the whole
array dim, so the (ROWS, K/TILE) scale block of a row group spans every tile
column and stays resident in VMEM across the inner tile loop; step j writes
its column into lane j (``put_lane``).  The grouped GEMMs read scale columns
back out of such whole-row blocks with ``take_index``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fp8 import E4M3, E4M3_MAX, TILE
from repro.kernels import checked_interpret, out_struct

ROWS = 128  # token rows per block


def kernel_po2_scale(amax):
    """Exact po2 scale from an f32 amax — the in-kernel twin of
    ``core.fp8.po2_scale``.

    XLA's f32 ``exp2`` is not correctly rounded for |exp| >= 13, so the
    original ``jnp.exp2(exp)`` epilogues could emit scales that are NOT exact
    powers of two at large/small amax — silently breaking the scaling-aware
    transpose contract (the same latent bug ``po2_scale`` fixed with ldexp).
    Here the scale is BIT-CONSTRUCTED from the integer exponent (exact for
    exp in [-126, 126], i.e. every clamped value), which also lowers to plain
    integer VPU ops on TPU."""
    safe = jnp.maximum(amax, jnp.float32(1e-38))
    exp = jnp.clip(jnp.ceil(jnp.log2(safe / E4M3_MAX)), -126.0, 126.0)
    bits = (exp.astype(jnp.int32) + 127) << 23
    s = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return jnp.where(amax > 0, s, jnp.float32(1.0))


def take_index(blk, i, axis: int):
    """Index ``i`` of ``axis`` of an f32 block, keeping the axis (size 1).

    A mask and a sum instead of a dynamic lane/sublane slice: exact, since
    every other term is zero, and it lowers to plain VPU ops."""
    idx = jax.lax.broadcasted_iota(jnp.int32, blk.shape, axis)
    return jnp.sum(jnp.where(idx == i, blk, 0.0), axis=axis, keepdims=True)


def put_lane(ref, j, col):
    """Write the (R, 1) column ``col`` into lane ``j`` of the (R, n) block
    ``ref``, leaving the other lanes as they are."""
    cur = ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, cur.shape, cur.ndim - 1)
    ref[...] = jnp.where(lane == j, col, cur)


def _quantize_kernel(x_ref, data_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                     # (ROWS, TILE)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)     # (ROWS, 1)
    s = kernel_po2_scale(amax)
    y = jnp.clip(x / s, -E4M3_MAX, E4M3_MAX)
    data_ref[...] = y.astype(E4M3)
    put_lane(scale_ref, pl.program_id(1), s)


def quantize_rowwise_pallas(x: jax.Array, *, interpret: bool):
    """x: (M, K) -> (data (M, K) e4m3, scale (M, K/TILE) f32 po2)."""
    M, K = x.shape
    assert M % ROWS == 0 and K % TILE == 0, (M, K)
    nb = K // TILE
    out_shapes = (
        out_struct((M, K), E4M3, x),
        out_struct((M, nb), jnp.float32, x),
    )
    return pl.pallas_call(
        _quantize_kernel,
        grid=(M // ROWS, nb),
        in_specs=[pl.BlockSpec((ROWS, TILE), lambda i, j: (i, j))],
        out_specs=(
            pl.BlockSpec((ROWS, TILE), lambda i, j: (i, j)),
            pl.BlockSpec((ROWS, nb), lambda i, j: (i, 0)),
        ),
        out_shape=out_shapes,
        interpret=checked_interpret(interpret),
    )(x)
