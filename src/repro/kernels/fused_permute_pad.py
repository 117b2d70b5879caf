"""Pallas TPU kernel: fused permute + padding for FP8 payload+scales (§3.3.1).

Reorders dispatched tokens so each expert's rows are contiguous AND pads each
expert group to a multiple of 128 rows (the TPU MXU alignment; the paper pads
to 16 for Hopper tensor cores) — in a single pass over HBM.  The row map is
scalar-prefetched into SMEM (`PrefetchScalarGridSpec`), so the BlockSpec index
map can route each output row to its source row with the DMA engine double-
buffering row fetches across grid steps; padding rows (map == -1) are written
as zeros by masking in-kernel.

A TPU block's last two dims must be (8, 128)-aligned or whole, so payload
and scales are viewed as (rows, 1, width): one row per grid step is then a
(1, 1, width) block whose last two dims are whole.

The same kernel runs the backward unpermute+unpad with the inverse map.
Payload and its (1,TILE) scale column move together — data + scales in one
kernel, two fewer HBM round trips than separate permute/pad/scale-copy ops.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import checked_interpret, out_struct


def _permute_kernel(idx_ref, x_ref, s_ref, xo_ref, so_ref):
    valid = idx_ref[pl.program_id(0)] >= 0

    @pl.when(valid)
    def _copy():
        xo_ref[...] = x_ref[...]
        so_ref[...] = s_ref[...]

    @pl.when(jnp.logical_not(valid))
    def _pad():
        xo_ref[...] = jnp.zeros_like(xo_ref)
        # padding scale is 1.0 so a downstream dequant of a zero payload
        # stays 0
        so_ref[...] = jnp.ones_like(so_ref)


def fused_permute_pad_pallas(x, s, row_map, n_out, *, interpret: bool):
    """x: (T, D) payload; s: (T, Ds) scales; row_map: (n_out,) int32 source row
    for each output row (-1 = padding).  Returns ((n_out, D), (n_out, Ds))."""
    T, D = x.shape
    Ds = s.shape[1]

    def src_map(i, idx_ref):
        return (jnp.maximum(idx_ref[i], 0), 0, 0)

    def dst_map(i, idx_ref):
        return (i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out,),
        in_specs=[
            pl.BlockSpec((1, 1, D), src_map),
            pl.BlockSpec((1, 1, Ds), src_map),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, D), dst_map),
            pl.BlockSpec((1, 1, Ds), dst_map),
        ),
    )
    xo, so = pl.pallas_call(
        _permute_kernel,
        grid_spec=grid_spec,
        out_shape=(
            out_struct((n_out, 1, D), x.dtype, x, s, row_map),
            out_struct((n_out, 1, Ds), s.dtype, x, s, row_map),
        ),
        interpret=checked_interpret(interpret),
    )(row_map, x.reshape(T, 1, D), s.reshape(T, 1, Ds))
    return xo.reshape(n_out, D), so.reshape(n_out, Ds)
