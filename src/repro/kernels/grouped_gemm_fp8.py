"""Pallas TPU kernels: FP8 grouped GEMM with per-tile scaling (DeepGEMM-on-TPU).

out[e] = (x[e] . sx[e]) @ (w[e] . sw[e])   for every expert e, where
  x  : (E, C, K)  e4m3, row-wise (1,TILE) scales sx (E, C, K/TILE)
  w  : (E, K, N)  e4m3, (TILE,TILE) block scales  sw (E, K/TILE, N/TILE)
  out: (E, C, N)  bf16

Grid: (E, C/BM, N/BN, K/BK) with BK == TILE so each K-step contributes one
scale product; partials accumulate in an f32 VMEM scratch (MXU contract:
fp8 x fp8 -> f32).  Block shapes are 128-aligned for the MXU; x/w blocks
stream HBM->VMEM once per (m,n,k) tile visit with the accumulator resident
across the K loop.

Two layouts:

* PADDED (the seed): every expert is padded to the full capacity C; padding
  rows are zero and contribute nothing, but their tiles still ride through
  the MXU.
* MASKED (DeepGEMM/LightLLM ``masked_group_gemm`` layout): a per-expert
  ``masked_m`` count vector (int32 (E,), scalar-prefetched into SMEM) tells
  each M-tile whether ANY of its rows are live; tiles with
  ``m * BM >= masked_m[e]`` skip the dot+scale work entirely via ``pl.when``
  and write zeros in the epilogue, so expert-load imbalance becomes a
  compute no-op instead of padded-tile MXU work.  ``expected_m`` is a STATIC
  tuning hint (the per-expert load the caller expects, e.g.
  ``ceil(T * top_k / E)``): it sizes the FLOPs/bytes model in
  ``benchmarks/masked_moe_ab.py`` and lets the ``ops.py`` wrappers fall back
  to the padded kernel when ``expected_m >= C`` (masking would only add
  scalar-prefetch overhead).  Masking is TILE-GRANULAR: rows beyond
  ``masked_m[e]`` inside a partially-live tile are computed from whatever
  payload is there, so callers that need row-exact zeros must zero-pad the
  dead rows (the fused permute+pad dispatch layout guarantees this).

Scales ride whole-row blocks (a TPU block's last dim must be a 128-multiple
or the whole array dim): the activation-scale block (1, BM, K/TILE) and the
weight-scale block (1, K/TILE, N/TILE) stay resident while the K loop
selects its column / element (``quantize.take_index``), and a quantizing
epilogue writes its scale column into lane n of a resident (1, BM, N/BN)
block (``quantize.put_lane``).

The masked GEMM-1 variant fuses the inter-GEMM SwiGLU + row-wise e4m3
re-quantize into the ``k == nk-1`` epilogue (paper §3.3.2 taken into the
kernel layer): gate/up column tiles accumulate in two scratches, the
epilogue rounds both through bf16 (bit-identical to the unfused
bf16-island h), applies silu(gate)*up and quantizes per (row, TILE)-tile —
the expert intermediate never materializes in bf16 in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fp8 import E4M3, E4M3_MAX, TILE
from repro.kernels import checked_interpret, out_struct
from repro.kernels.quantize import kernel_po2_scale, put_lane, take_index

BM = 128
BN = 128
BK = TILE  # must equal the scale tile


def _scales(sx_ref, sw_ref, n, k):
    """(BM, 1) activation scales of K-tile k and the (1, 1) weight scale of
    block (k, n), read out of their resident whole-row blocks."""
    sx = take_index(sx_ref[0], k, 1)
    sw = take_index(take_index(sw_ref[0], k, 0), n, 1)
    return sx, sw


def _scaled_partial(x_ref, w, sx_ref, sw_ref, n, k):
    """One K-step: the fp8 tile product (f32 accumulate) times its scales."""
    x = x_ref[0].astype(jnp.float32)                   # (BM, BK) fp8 payload
    partial = jax.lax.dot(x, w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)  # f32 accum
    sx, sw = _scales(sx_ref, sw_ref, n, k)
    return partial * (sx * sw)


def _gg_kernel(x_ref, sx_ref, w_ref, sw_ref, o_ref, acc_ref, *, nk: int):
    n, k = pl.program_id(2), pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _scaled_partial(x_ref, w_ref[0], sx_ref, sw_ref, n, k)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def _quant_tile(y, o_ref, os_ref, n):
    """Row-wise e4m3 + po2-scale quantization of a (BM, BN=TILE) f32 tile;
    the scale column goes to lane n of the resident scale block."""
    amax = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    s = kernel_po2_scale(amax)
    o_ref[0, ...] = jnp.clip(y / s, -E4M3_MAX, E4M3_MAX).astype(E4M3)
    put_lane(os_ref.at[0], n, s)


def _gg_quant_kernel(x_ref, sx_ref, w_ref, sw_ref, o_ref, os_ref, acc_ref,
                     *, nk: int):
    """Same as _gg_kernel but the epilogue quantizes the (BM, BN=TILE) output
    tile to e4m3 + a po2 scale column — the 'fused epilogue quantization' that
    keeps Dgrad outputs in FP8 without an explicit cast kernel (§3.2)."""
    n, k = pl.program_id(2), pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _scaled_partial(x_ref, w_ref[0], sx_ref, sw_ref, n, k)

    @pl.when(k == nk - 1)
    def _done():
        _quant_tile(acc_ref[...], o_ref, os_ref, n)


def _assert_quant_out_tiling():
    """The quantizing epilogues compute ONE scale per (row, BN-tile) and the
    wrappers expose it as a row-wise (..., 1, TILE) QTensor whose scale shape
    is N // TILE.  That is only correct while BN == TILE — if the block
    shapes ever diverge the scale metadata would be silently wrong, so the
    mismatch must fail at trace time."""
    assert BN == TILE, (
        f"quant-out epilogue requires BN == TILE (got BN={BN}, TILE={TILE}): "
        "the per-(row, BN-tile) scales are exposed as (1, TILE) row tiles")


def _in_specs(nk: int, nn: int, prefetch: bool):
    """x / sx / w / sw block specs (index maps see the scalar-prefetch ref
    as a trailing arg when ``prefetch``)."""
    def im(f):
        return (lambda e, m, n, k, mm: f(e, m, n, k)) if prefetch else f
    return [
        pl.BlockSpec((1, BM, BK), im(lambda e, m, n, k: (e, m, k))),
        pl.BlockSpec((1, BM, nk), im(lambda e, m, n, k: (e, m, 0))),
        pl.BlockSpec((1, BK, BN), im(lambda e, m, n, k: (e, k, n))),
        pl.BlockSpec((1, nk, nn), im(lambda e, m, n, k: (e, 0, 0))),
    ]


def _out_specs(nn: int, quant_out: bool, prefetch: bool):
    def im(f):
        return (lambda e, m, n, k, mm: f(e, m, n, k)) if prefetch else f
    data = pl.BlockSpec((1, BM, BN), im(lambda e, m, n, k: (e, m, n)))
    if not quant_out:
        return data
    return (data, pl.BlockSpec((1, BM, nn), im(lambda e, m, n, k: (e, m, 0))))


def _out_shape(E, C, N, out_dtype, quant_out: bool, operands):
    if not quant_out:
        return out_struct((E, C, N), out_dtype, *operands)
    _assert_quant_out_tiling()
    return (out_struct((E, C, N), E4M3, *operands),
            out_struct((E, C, N // BN), jnp.float32, *operands))


def grouped_gemm_fp8_pallas(x, sx, w, sw, *, out_dtype=jnp.bfloat16,
                            quant_out: bool = False, interpret: bool):
    E, C, K = x.shape
    _, _, N = w.shape
    assert C % BM == 0 and N % BN == 0 and K % BK == 0, (C, K, N)
    nk, nn = K // BK, N // BN
    kernel = _gg_quant_kernel if quant_out else _gg_kernel
    return pl.pallas_call(
        functools.partial(kernel, nk=nk),
        grid=(E, C // BM, nn, nk),
        in_specs=_in_specs(nk, nn, prefetch=False),
        out_specs=_out_specs(nn, quant_out, prefetch=False),
        out_shape=_out_shape(E, C, N, out_dtype, quant_out, (x, sx, w, sw)),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)],
        interpret=checked_interpret(interpret),
    )(x, sx, w, sw)


# ---------------------------------------------------------------------------
# Masked layout.  masked_m rides scalar prefetch (SMEM) so the per-tile
# liveness predicate is available before the tile body runs.
# ---------------------------------------------------------------------------
def _gg_masked_kernel(mm_ref, x_ref, sx_ref, w_ref, sw_ref, o_ref, acc_ref,
                      *, nk: int):
    e, m, n, k = (pl.program_id(i) for i in range(4))
    live = m * BM < mm_ref[e]

    @pl.when(live & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        acc_ref[...] += _scaled_partial(x_ref, w_ref[0], sx_ref, sw_ref, n, k)

    @pl.when(k == nk - 1)
    def _done():
        # dead tiles write zeros — bitwise what the padded kernel produces
        # for zero-padded rows, so masked == padded on the whole buffer
        # whenever rows beyond masked_m are zero (the dispatch layout).
        o_ref[0, ...] = jnp.where(live, acc_ref[...], 0.0).astype(o_ref.dtype)


def _gg_masked_quant_kernel(mm_ref, x_ref, sx_ref, w_ref, sw_ref, o_ref,
                            os_ref, acc_ref, *, nk: int):
    e, m, n, k = (pl.program_id(i) for i in range(4))
    live = m * BM < mm_ref[e]

    @pl.when(live & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        acc_ref[...] += _scaled_partial(x_ref, w_ref[0], sx_ref, sw_ref, n, k)

    @pl.when(k == nk - 1)
    def _done():
        # dead tiles: acc==0 -> amax==0 -> scale 1.0, payload 0 — the exact
        # bits the padded quantizing epilogue emits for zero rows.
        _quant_tile(jnp.where(live, acc_ref[...], 0.0), o_ref, os_ref, n)


def _gg_masked_swiglu_quant_kernel(mm_ref, x_ref, sx_ref, w_ref, sw_ref,
                                   o_ref, os_ref, accg_ref, accu_ref,
                                   *, nk: int):
    """Masked grouped GEMM-1 with the SwiGLU + row-wise re-quantize fused
    into the last K-step: w13 arrives reshaped (E, K, 2, F) so ONE operand
    block carries both the gate (half 0) and up (half 1) column tiles, and
    its scales (E, K/TILE, 2*F/TILE) hold the gate tiles' scales in columns
    [0, F/TILE) and the up tiles' after them.  The epilogue rounds both
    accumulators through bf16 first — bit-identical to the unfused path's
    materialized bf16 island h — then quantizes silu(gate)*up per
    (row, TILE)-tile."""
    e, m, n, k = (pl.program_id(i) for i in range(4))
    nf = pl.num_programs(2)
    live = m * BM < mm_ref[e]

    @pl.when(live & (k == 0))
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    @pl.when(live)
    def _step():
        accg_ref[...] += _scaled_partial(x_ref, w_ref[0, :, 0, :], sx_ref,
                                         sw_ref, n, k)        # gate cols
        accu_ref[...] += _scaled_partial(x_ref, w_ref[0, :, 1, :], sx_ref,
                                         sw_ref, nf + n, k)   # up cols

    @pl.when(k == nk - 1)
    def _done():
        g = jnp.where(live, accg_ref[...], 0.0)
        u = jnp.where(live, accu_ref[...], 0.0)
        # bf16 round-trip = the paper's deliberate BF16 island, kept so the
        # fused epilogue is BITWISE the unfused h -> swiglu+quant kernel pair
        g = g.astype(jnp.bfloat16).astype(jnp.float32)
        u = u.astype(jnp.bfloat16).astype(jnp.float32)
        _quant_tile((g * jax.lax.logistic(g)) * u, o_ref, os_ref, n)


def masked_grouped_gemm_fp8_pallas(x, sx, w, sw, masked_m, *,
                                   out_dtype=jnp.bfloat16,
                                   quant_out: bool = False,
                                   interpret: bool):
    """Masked grouped GEMM: tiles with m*BM >= masked_m[e] are compute
    no-ops (zeros written in the epilogue)."""
    E, C, K = x.shape
    _, _, N = w.shape
    assert C % BM == 0 and N % BN == 0 and K % BK == 0, (C, K, N)
    assert masked_m.shape == (E,) and masked_m.dtype == jnp.int32, masked_m
    nk, nn = K // BK, N // BN
    kernel = _gg_masked_quant_kernel if quant_out else _gg_masked_kernel
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(E, C // BM, nn, nk),
        in_specs=_in_specs(nk, nn, prefetch=True),
        out_specs=_out_specs(nn, quant_out, prefetch=True),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32)])
    return pl.pallas_call(
        functools.partial(kernel, nk=nk),
        grid_spec=gs,
        out_shape=_out_shape(E, C, N, out_dtype, quant_out,
                             (x, sx, w, sw, masked_m)),
        interpret=checked_interpret(interpret),
    )(masked_m, x, sx, w, sw)


def masked_grouped_gemm_swiglu_quant_pallas(x, sx, w13, sw13, masked_m, *,
                                            interpret: bool):
    """Masked grouped GEMM-1 with fused SwiGLU + e4m3 re-quantize epilogue.

    x    : (E, C, K) e4m3, row-wise scales sx (E, C, K/TILE)
    w13  : (E, K, 2F) e4m3 [gate | up], block scales sw13 (E, K/T, 2F/T)
    out  : (data (E, C, F) e4m3, scale (E, C, F/TILE) f32)

    The [gate | up] halves are exposed to the kernel through a zero-copy
    (E, K, 2, F) reshape, so ONE HBM operand (one BlockSpec) feeds both
    accumulators — no duplicate operand declaration.
    """
    E, C, K = x.shape
    twoF = w13.shape[-1]
    F = twoF // 2
    assert C % BM == 0 and F % BN == 0 and K % BK == 0, (C, K, F)
    assert masked_m.shape == (E,) and masked_m.dtype == jnp.int32, masked_m
    nk, nf = K // BK, F // BN
    w4 = w13.reshape(E, K, 2, F)
    specs = _in_specs(nk, 2 * nf, prefetch=True)
    specs[2] = pl.BlockSpec((1, BK, 2, BN), lambda e, m, n, k, mm: (e, k, 0, n))
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(E, C // BM, nf, nk),
        in_specs=specs,
        out_specs=_out_specs(nf, True, prefetch=True),
        scratch_shapes=[pltpu.VMEM((BM, BN), jnp.float32),
                        pltpu.VMEM((BM, BN), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gg_masked_swiglu_quant_kernel, nk=nk),
        grid_spec=gs,
        out_shape=_out_shape(E, C, F, None, True,
                             (x, sx, w13, sw13, masked_m)),
        interpret=checked_interpret(interpret),
    )(masked_m, x, sx, w4, sw13)
