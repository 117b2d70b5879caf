"""Pallas TPU kernel: FP8 grouped GEMM, NT layout (Wgrad form).

out[e] = (a[e] . sa[e]) @ (b[e] . sb[e])^T  with contraction over the LAST
axis of both operands:
  a  : (E, M, C) e4m3, row-wise (1,TILE) scales sa (E, M, C/TILE)
  b  : (E, N, C) e4m3, row-wise (1,TILE) scales sb (E, N, C/TILE)
  out: (E, M, N) f32 (weight gradients accumulate in f32)

This is exactly the shape the scaling-aware direct transpose produces: Wgrad
consumes T(activations) and T(grad) — both row-tiled over the token axis —
with no dequantize/requantize anywhere (paper §3.1/§3.2).

The MASKED variant takes the per-expert live-token count vector ``masked_m``
(the same counts the masked forward GEMMs use): here the token axis is the
CONTRACTION axis, so K-steps with ``k * BK >= masked_m[e]`` are skipped —
their padded-token columns are all zero and contribute nothing, which makes
the skip bitwise-invisible (x + 0.0 == x in f32 for finite x) while saving
the full MXU visit.  Partially-live K-tiles are computed whole; callers must
zero-pad dead token columns (the direct transpose of the zero-padded
dispatch layout guarantees this).

Scales ride whole-row blocks, as in ``grouped_gemm_fp8``: sa's (1, BM, C/TILE)
block gives K-step k its column, and sb goes in transposed, (E, C/TILE, N),
so its (1, C/TILE, BN) block gives K-step k a (1, BN) row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fp8 import TILE
from repro.kernels import checked_interpret, out_struct
from repro.kernels.quantize import take_index

BM = 128
BN = 128
BK = TILE


def _scaled_partial(a_ref, sa_ref, b_ref, sbt_ref, k):
    """One contraction step: the fp8 tile product times its scales."""
    a = a_ref[0].astype(jnp.float32)                   # (BM, BK)
    b = b_ref[0].astype(jnp.float32)                   # (BN, BK)
    partial = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)           # (BM, BN) f32
    sa = take_index(sa_ref[0], k, 1)                   # (BM, 1)
    sb = take_index(sbt_ref[0], k, 0)                  # (1, BN)
    return partial * (sa * sb)


def _gg_nt_kernel(a_ref, sa_ref, b_ref, sbt_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _scaled_partial(a_ref, sa_ref, b_ref, sbt_ref, k)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def _specs(nk: int, prefetch: bool):
    def im(f):
        return (lambda e, m, n, k, mm: f(e, m, n, k)) if prefetch else f
    ins = [
        pl.BlockSpec((1, BM, BK), im(lambda e, m, n, k: (e, m, k))),
        pl.BlockSpec((1, BM, nk), im(lambda e, m, n, k: (e, m, 0))),
        pl.BlockSpec((1, BN, BK), im(lambda e, m, n, k: (e, n, k))),
        pl.BlockSpec((1, nk, BN), im(lambda e, m, n, k: (e, 0, n))),
    ]
    return ins, pl.BlockSpec((1, BM, BN), im(lambda e, m, n, k: (e, m, n)))


def grouped_gemm_nt_fp8_pallas(a, sa, b, sb, *, out_dtype=jnp.float32,
                               interpret: bool):
    E, M, C = a.shape
    _, N, _ = b.shape
    assert M % BM == 0 and N % BN == 0 and C % BK == 0, (M, N, C)
    nk = C // BK
    in_specs, out_specs = _specs(nk, prefetch=False)
    return pl.pallas_call(
        functools.partial(_gg_nt_kernel, nk=nk),
        grid=(E, M // BM, N // BN, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_struct((E, M, N), out_dtype, a, sa, b, sb),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=checked_interpret(interpret),
    )(a, sa, b, jnp.swapaxes(sb, 1, 2))


# ---------------------------------------------------------------------------
# Masked layout: skip contraction steps over dead token tiles.
# ---------------------------------------------------------------------------
def _gg_nt_masked_kernel(mm_ref, a_ref, sa_ref, b_ref, sbt_ref, o_ref,
                         acc_ref, *, nk: int):
    e = pl.program_id(0)
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k * BK < mm_ref[e])
    def _step():
        acc_ref[...] += _scaled_partial(a_ref, sa_ref, b_ref, sbt_ref, k)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def masked_grouped_gemm_nt_fp8_pallas(a, sa, b, sb, masked_m, *,
                                      out_dtype=jnp.float32,
                                      interpret: bool):
    """Masked NT grouped GEMM: the token (contraction) axis is masked —
    K-steps beyond expert e's live count contribute nothing and are skipped.
    Bitwise-equal to the padded kernel when dead token columns are zero."""
    E, M, C = a.shape
    _, N, _ = b.shape
    assert M % BM == 0 and N % BN == 0 and C % BK == 0, (M, N, C)
    assert masked_m.shape == (E,) and masked_m.dtype == jnp.int32, masked_m
    nk = C // BK
    in_specs, out_specs = _specs(nk, prefetch=True)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(E, M // BM, N // BN, nk),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gg_nt_masked_kernel, nk=nk),
        grid_spec=gs,
        out_shape=out_struct((E, M, N), out_dtype, a, sa, b, sb, masked_m),
        interpret=checked_interpret(interpret),
    )(masked_m, a, sa, b, jnp.swapaxes(sb, 1, 2))
