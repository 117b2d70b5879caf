"""Tile-quantized FP8 tensors (QTensor) and the quantize/dequantize ops.

Quantization follows the paper (Eq. 2): per-tile scaling over 128 contiguous
elements, scale = po2(ceil)(amax / 448) by default (power-of-two scales are the
enabler for the scaling-aware transpose, §3.1).  ``scale_mode='linear'``
reproduces the conventional TE/DeepSeek recipe (s = amax/448, arbitrary float)
used as the double-quantization-error baseline.

A ``QTensor`` carries:
  data  : fp8 payload (e4m3 by default)
  scale : f32 power-of-two scales, one per tile; shape[i] = data.shape[i]/tile[i]
  tile  : static per-axis tile sizes, e.g. (1, 128) row-wise, (128, 128) weights

Tile-metadata convention (normative — every producer and consumer in the
repo follows it, and ``tests/test_kernels.py`` asserts it on the kernel
wrappers):

  * ``len(tile) == data.ndim`` always.  Leading batch/expert axes get
    explicit 1s — e.g. a (E, C, K) row-tiled activation is ``(1, 1, TILE)``,
    never a 2-tuple broadcast against a 3-D payload.
  * Row-wise tiles are ``(1,) * (ndim - 1) + (TILE,)`` — use ``row_tile``.
  * Weight blocks are ``(1,) * (ndim - 2) + (TILE, TILE)``.
  * ``scale.shape[i] * tile[i] == data.shape[i]`` for every axis
    (``_scale_shape`` enforces divisibility at quantize time).

Every quantize/dequantize call is recorded on the active CastLedger (see
``casts.py``) — this is how the 12-vs-2 cast accounting of Fig. 2 is asserted.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import casts
from repro.core.fp8 import E4M3, FMT_MAX, TILE, po2_scale


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QTensor:
    data: jax.Array
    scale: jax.Array
    tile: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))

    @property
    def shape(self):
        return self.data.shape

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def dtype(self):
        return self.data.dtype

    def nbytes_model(self) -> int:
        """Bytes this tensor occupies (payload + scales) — used by memory bench."""
        return self.data.size * 1 + self.scale.size * 4


# ---------------------------------------------------------------------------
# checkpoint_name tags for the activation-residency plan (train/memory.py).
# The MemoryPlan policies select saveables BY NAME (jax.checkpoint_policies.
# save_only_these_names): 'full' keeps the BF16 stage boundaries, while
# 'fp8_resident' keeps only the QTensor stage outputs — so the backward
# recomputes from e4m3+scale instead of wide bf16 activations.
# ---------------------------------------------------------------------------
FP8_SAVE_NAMES = ("fp8_qx_data", "fp8_qx_scale",      # dispatched FFN input
                  "fp8_qa_data", "fp8_qa_scale")      # post-activation GEMM2 in
BF16_STAGE_NAMES = ("stage_attn_out",                 # attn residual-out
                    "stage_ffn_in",                   # post-ln2 FFN input
                    "stage_ffn_h",                    # the bf16 island h
                    "stage_expert_out")               # expert out (combine in)


def tag_saveable(x, name: str):
    """Name a tensor for the residency policies (value-identity; None passes).

    bf16 tensors are pinned with an explicit reduce_precision(8, 7) first:
    XLA keeps excess precision through bf16 fusions, so without the pin a
    policy that SAVES the tensor (materializing real bf16) would compute
    slightly different bits than one that recomputes it — the pin makes
    every residency policy evaluate the identical function (jax inserts the
    same op on saved-residual producers; see jax#22244)."""
    if x is None:
        return None
    from jax.ad_checkpoint import checkpoint_name
    if x.dtype == jnp.bfloat16:
        x = jax.lax.reduce_precision(x, 8, 7)
    return checkpoint_name(x, name)


def tag_qtensor(q: "QTensor", name: str) -> "QTensor":
    """Tag a QTensor's payload + scales as '<name>_data' / '<name>_scale'.

    The fp8 payload is tagged AS ITS uint8 BIT PATTERN (the same bitcast
    idiom as the fused wire messages): jax's remat inserts
    reduce_precision(finfo(dtype)) on saved-residual producers, which is
    ill-defined for the no-inf e4m3fn format (overflow lanes turn NaN under
    XLA fusion) — integer residuals skip that machinery and the bits are
    the value anyway.  Only the fwd rules of the FFN/dispatch custom_vjps
    call this, so autodiff never differentiates through the bitcast."""
    u8 = jax.lax.bitcast_convert_type(q.data, jnp.uint8)
    u8 = tag_saveable(u8, f"{name}_data")
    data = jax.lax.bitcast_convert_type(u8, q.data.dtype)
    return QTensor(data, tag_saveable(q.scale, f"{name}_scale"), q.tile)


# ---------------------------------------------------------------------------
# FP8 health stats for the numerics guardrails (train/guards.py).
#
# When a collector is armed (train_step traces under `collect_stats()`),
# the instrumented sites record a (N_SITES, 2) f32 matrix — their row is
# [saturation fraction, underflow-flush fraction] of their tensor, every
# other row zero; max-merge keeps per-site resolution.  The recorded
# values are
# TRACERS, and recording must happen in a trace region that can hand them
# back out: any enclosing lax.scan body / jax.checkpoint block / shard_map
# body drains its own records into an explicit output before returning
# (`drain_stats` + `reinject_stats` at the outer level) — models/lm.py
# threads them through every stack driver and both MLP/MoE shard_maps.
#
# Crucially, recording must sit OUTSIDE any custom_vjp: fwd/bwd rules are
# traced to their own jaxprs, so a record made inside one is a foreign
# tracer by the time the surrounding region drains (UnexpectedTracerError).
# quantize() itself (which runs inside the entry/FFN custom_vjps) therefore
# never records; the entry sites record via `record_entry_stats` at their
# CALL sites, and the gradient wire records inside grad_comm's
# quantize_bucket (plain code in the train-step body).  The backward-island
# quantizes (q_bwd_*) are covered by the grad-norm/nonfinite guards one
# reduction later instead.  With no collector armed this machinery adds
# ZERO ops — the default jaxpr is bitwise-unchanged.
# ---------------------------------------------------------------------------
STATS_LEN = 2                      # [sat_frac, flush_frac], max-merged
# Instrumented quantize sites, one row of the collected matrix each.  The
# collected value is a (N_SITES, STATS_LEN) f32 matrix — PR 7 carried a
# single max-merged (2,) vector; the per-site rows ride the SAME carries
# (every drain/reinject threading point is shape-generic), so site
# resolution costs no extra threading and no extra host syncs.  The scalar
# guard thresholds keep their old meaning as the max over sites, while the
# obs layer exports the full matrix as a per-site time series (the input
# the ROADMAP's guard-driven adaptive precision controller needs).
STAT_SITES = ("q_entry_mlp",       # dense-MLP / shared-expert entry quantize
              "q_entry_moe",       # MoE dispatch entry quantize
              "dp_wire")           # DP gradient-wire bucket quantize
N_SITES = len(STAT_SITES)
_SITE_ROW = {t: i for i, t in enumerate(STAT_SITES)}
_SITE_ROW["q_entry"] = 0           # legacy alias (pre-split call sites)
STATS_TAGS = frozenset(_SITE_ROW)

_QSTATS: contextvars.ContextVar[Optional["QuantStatsCollector"]] = \
    contextvars.ContextVar("quant_stats", default=None)


class QuantStatsCollector:
    def __init__(self):
        self.vals: List[jax.Array] = []


def stats_armed() -> bool:
    return _QSTATS.get() is not None


def zero_stats() -> jax.Array:
    return jnp.zeros((N_SITES, STATS_LEN), jnp.float32)


def site_maxima(stats: jax.Array) -> jax.Array:
    """(N_SITES, STATS_LEN) -> (STATS_LEN,) max over sites — the scalar
    [sat_frac, flush_frac] pair the guard thresholds compare against
    (identical to the pre-per-site collector's merged value)."""
    return jnp.max(jnp.asarray(stats, jnp.float32), axis=0)


def record_stat_pair(tag: str, sat_frac, flush_frac) -> None:
    col = _QSTATS.get()
    if col is not None:
        pair = jnp.stack([jnp.asarray(sat_frac, jnp.float32),
                          jnp.asarray(flush_frac, jnp.float32)])
        col.vals.append(zero_stats().at[_SITE_ROW[tag]].set(pair))


def drain_stats() -> jax.Array:
    """Max-merge and CLEAR the collected stats (call inside the trace
    region whose records you are extracting)."""
    col = _QSTATS.get()
    if col is None or not col.vals:
        return zero_stats()
    out = col.vals[0]
    for v in col.vals[1:]:
        out = jnp.maximum(out, v)
    col.vals.clear()
    return out


def reinject_stats(vec) -> None:
    """Re-record a drained stats vector at the CURRENT trace level (after
    a scan / checkpoint block returned it as an explicit output)."""
    col = _QSTATS.get()
    if col is not None:
        col.vals.append(jnp.asarray(vec, jnp.float32))


@contextlib.contextmanager
def collect_stats():
    col = QuantStatsCollector()
    tok = _QSTATS.set(col)
    try:
        yield col
    finally:
        _QSTATS.reset(tok)


def _maybe_record_stats(tag: str, xf, data, fmax: float) -> None:
    """sat = pre-clip overflow fraction; flush = nonzero inputs whose fp8
    encoding flushed to zero (below the subnormal floor).  `xf` is the
    already-scaled tensor, `data` its fp8 payload.  Callers must sit outside
    any custom_vjp (see the stats block comment)."""
    if _QSTATS.get() is None or tag not in STATS_TAGS:
        return
    xa = jnp.abs(xf.astype(jnp.float32))
    sat = jnp.mean((xa > fmax).astype(jnp.float32))
    flush = jnp.mean(((data.astype(jnp.float32) == 0) & (xa > 0)
                      ).astype(jnp.float32))
    record_stat_pair(tag, sat, flush)


def record_entry_stats(tag: str, x, q: Optional["QTensor"] = None,
                       scale_mode: str = "po2", fmt=E4M3) -> None:
    """Record sat/flush for a forward entry quantize from its CALL SITE
    (outside the custom_vjp whose fwd rule performed the quantization).

    With `q` LAYOUT-ALIGNED to x (quantize_entry's return), its payload and
    scales are reused; without (the MoE dispatch returns a permuted/padded
    QTensor), the row-wise scale + payload are recomputed — one amax +
    cast pass, and only while a collector is armed."""
    if _QSTATS.get() is None or tag not in STATS_TAGS:
        return
    fmax = FMT_MAX[fmt]
    tile = row_tile(x.ndim)
    if q is None:
        scale = compute_scale(x, tile, fmt, scale_mode)
    else:
        scale = q.scale
    xf = _tiled_op(x.astype(jnp.float32), scale, tile, lambda a, b: a / b)
    data = q.data if q is not None else \
        jnp.clip(xf, -fmax, fmax).astype(fmt)
    _maybe_record_stats(tag, xf, data, fmax)


def row_tile(ndim: int) -> Tuple[int, ...]:
    """Canonical row-wise tile metadata for an ndim-D payload: last axis in
    TILE-wide tiles, every other axis at element granularity."""
    return (1,) * (ndim - 1) + (TILE,)


def _scale_shape(shape, tile):
    assert len(shape) == len(tile), (shape, tile)
    for s, t in zip(shape, tile):
        if s % t:
            raise ValueError(f"shape {shape} not divisible by tile {tile}")
    return tuple(s // t for s, t in zip(shape, tile))


def _split_shape(shape, tile):
    """(n0, n1, ...) -> interleaved (n0/t0, t0, ...) with 1s for the scale."""
    xs, ss = [], []
    for n, t in zip(shape, tile):
        if t == 1:
            xs.append(n)
            ss.append(n)
        else:
            xs.extend((n // t, t))
            ss.extend((n // t, 1))
    return tuple(xs), tuple(ss)


def _tiled_op(x, scale, tile, op):
    """x <op> per-tile-scale, the scale broadcast without relayouting x.

    A TPU tiles the last two axes of an array in memory (sublanes x lanes).
    Splitting the second-to-last axis into (n/t, t) moves no data (t is a
    multiple of the sublane tile); splitting the last (lane) axis changes
    the tiled layout: a copy of the whole tensor, or of a full-size
    upsampled scale, in every call.  So every tiled axis but the last is
    split, and along the last the small scale array is repeated to full
    width instead: the convert, the scaling and the broadcast then fuse into
    one pass that XLA feeds straight into a dot.  Row-wise tiles over
    several tiles of the last axis are split instead: there the repeated
    scale would be as large as x, and XLA's cost analysis of the training
    step counts fewer bytes for the split."""
    *lead, last = tile
    if last == 1 or (all(t == 1 for t in lead) and x.shape[-1] > last):
        xs, ss = _split_shape(x.shape, tile)
        return op(x.reshape(xs), scale.reshape(ss)).reshape(x.shape)
    xs, ss = _split_shape(x.shape[:-1], lead)
    out = op(x.reshape(xs + x.shape[-1:]),
             jnp.repeat(scale, last, axis=-1).reshape(ss + x.shape[-1:]))
    return out.reshape(x.shape)


def _tile_amax(x: jax.Array, tile) -> jax.Array:
    """amax over each tile; returns array of shape _scale_shape(x.shape, tile).

    Computed in the INPUT dtype (max is exact in any float format) and
    widened to f32 only at the reduced size — avoids materializing a full
    f32 copy of the tensor (§Perf iteration: memory-term)."""
    shp = []
    red_axes = []
    for ax, (n, t) in enumerate(zip(x.shape, tile)):
        if t == 1:
            shp.append(n)
        else:
            shp.extend((n // t, t))
            red_axes.append(len(shp) - 1)
    y = jnp.abs(x.reshape(shp))
    return jnp.max(y, axis=tuple(red_axes)).astype(jnp.float32)


def compute_scale(x: jax.Array, tile, fmt=E4M3, scale_mode: str = "po2") -> jax.Array:
    amax = _tile_amax(x, tile)
    fmax = FMT_MAX[fmt]
    if scale_mode == "po2":
        return po2_scale(amax, fmax)
    elif scale_mode == "linear":  # conventional recipe: s = amax / 448
        return jnp.where(amax > 0, amax / fmax, jnp.float32(1.0))
    raise ValueError(scale_mode)


def quantize(x: jax.Array, tile, fmt=E4M3, scale_mode: str = "po2",
             tag: str = "q", kind: str = "quantize") -> QTensor:
    """Quantize a dense tensor to per-tile fp8. Counted on the CastLedger.

    kind='quantize' is an explicit cast; kind='fused_quantize' marks a
    quantization folded into a surrounding kernel (not counted by Fig. 2)."""
    casts.record(kind, tag, x.size)
    from repro.runtime import fault_injection
    x = fault_injection.apply("activation", tag, x)
    scale = compute_scale(x, tile, fmt, scale_mode)
    fmax = FMT_MAX[fmt]
    if x.dtype == jnp.bfloat16 and scale_mode == "po2":
        # division by a power of two is EXACT in bf16, and bf16 -> e4m3
        # rounds identically to f32 -> e4m3 (e4m3's mantissa is shorter):
        # same bits as the f32 path at half the intermediate bytes.
        xf = _tiled_op(x, scale.astype(jnp.bfloat16), tile,
                       lambda a, b: a / b)
        data = jnp.clip(xf, jnp.bfloat16(-fmax), jnp.bfloat16(fmax)).astype(fmt)
    else:
        xf = _tiled_op(x.astype(jnp.float32), scale, tile, lambda a, b: a / b)
        data = jnp.clip(xf, -fmax, fmax).astype(fmt)
    return QTensor(data=data, scale=scale, tile=tuple(tile))


def quantize_rowwise(x: jax.Array, fmt=E4M3, scale_mode="po2", tag="q_row",
                     kind="quantize") -> QTensor:
    """1 x TILE tiles along the last axis (Fprop/Dgrad activation layout)."""
    return quantize(x, row_tile(x.ndim), fmt, scale_mode, tag=tag, kind=kind)


def quantize_colwise(x: jax.Array, fmt=E4M3, scale_mode="po2", tag="q_col") -> QTensor:
    """TILE x 1 tiles along the second-to-last axis (Wgrad layout, untransposed)."""
    tile = (1,) * (x.ndim - 2) + (TILE, 1)
    return quantize(x, tile, fmt, scale_mode, tag=tag)


def quantize_blockwise(w: jax.Array, fmt=E4M3, scale_mode="po2", tag="q_wblk") -> QTensor:
    """TILE x TILE blocks over the last two axes (weight layout, DeepGEMM-style)."""
    tile = (1,) * (w.ndim - 2) + (TILE, TILE)
    return quantize(w, tile, fmt, scale_mode, tag=tag)


def dequantize(q: QTensor, dtype=jnp.bfloat16, tag: str = "dq",
               kind: str = "dequantize") -> jax.Array:
    """Counted on the CastLedger."""
    casts.record(kind, tag, q.data.size)
    return _dequantize_nocount(q, dtype)


def _dequantize_nocount(q: QTensor, dtype=jnp.float32) -> jax.Array:
    if dtype == jnp.bfloat16:
        # e4m3 -> bf16 is exact, and x * po2 is exact in bf16: skip the f32
        # intermediate (halves dequant bytes; bit-identical for po2 scales)
        return _tiled_op(q.data.astype(jnp.bfloat16),
                         q.scale.astype(jnp.bfloat16), q.tile,
                         lambda a, b: a * b)
    return _tiled_op(q.data.astype(jnp.float32), q.scale, q.tile,
                     lambda a, b: a * b).astype(dtype)


# ---------------------------------------------------------------------------
# FP8 GEMM contract.  The kernel consumes fp8 payloads + per-tile scales and
# accumulates in f32 (MXU contract); this XLA-path implementation upcasts at
# the MXU boundary — NOT a counted "cast" because no materialized Q/DQ tensor
# round-trips through HBM (the upcast lives inside the fused GEMM on TPU).
# ---------------------------------------------------------------------------
def qdot(qx: QTensor, qw: QTensor, out_dtype=jnp.bfloat16,
         precision=None) -> jax.Array:
    """(..., M, K) tile-(1,TILE) @ (K, N) tile-(TILE,TILE) -> (..., M, N).

    Contraction over the last axis of qx and first payload axis of qw.
    """
    xf = _dequantize_nocount(qx, jnp.float32)
    wf = _dequantize_nocount(qw, jnp.float32)
    out = jnp.matmul(xf, wf, precision=precision)
    return out.astype(out_dtype)


def qdot_general(qx: QTensor, qw: QTensor, dimension_numbers,
                 out_dtype=jnp.bfloat16, precision=None) -> jax.Array:
    xf = _dequantize_nocount(qx, jnp.float32)
    wf = _dequantize_nocount(qw, jnp.float32)
    out = jax.lax.dot_general(xf, wf, dimension_numbers, precision=precision)
    return out.astype(out_dtype)
