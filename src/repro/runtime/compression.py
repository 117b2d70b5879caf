"""FP8 gradient compression for the cross-pod data-parallel reduction.

The multi-pod mesh reduces gradients over the `pod` axis across the (slow)
inter-pod links.  Extending the paper's FP8-communication idea beyond the
MoE dispatch, `compressed_psum` performs the pod reduction on an e4m3
payload + po2 scales: reduce-scatter in FP8, local f32 accumulation,
all-gather in FP8 — halving inter-pod gradient bytes (plus 1/128 scale
overhead) at a quantization error bounded by the po2 tile quantizer.

Error feedback (residual carrying) keeps the compression unbiased over
steps: the quantization residual of step t is added back at step t+1.

NOTE: the TRAIN-path gradient reduction now lives in repro.dist
(DistPlan): bucketized, scale-agreed (no re-quantization of the reduced
value), ZeRO-1-sharded, packed into one uint8 message per bucket.  This
module remains the standalone psum-shaped primitive for the cross-pod hop
and the compression-error benches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fp8 import TILE, E4M3_MAX
from repro.core.quant import quantize_rowwise, _dequantize_nocount


def _q_flat(x):
    """Quantize an arbitrary tensor as flat (rows, TILE) e4m3 + scales."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % TILE
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.reshape(-1, TILE)
    q = quantize_rowwise(rows, tag="grad_compress", kind="fused_quantize")
    return q, n, pad


def _dq_flat(q, n, pad, shape, dtype):
    flat = _dequantize_nocount(q, jnp.float32).reshape(-1)
    if pad:
        flat = flat[:n]
    return flat.reshape(shape).astype(dtype)


def compressed_psum(x, axis_name: str):
    """psum over `axis_name` with FP8 wire format (inside shard_map).

    reduce_scatter(e4m3) -> local dequant+sum in f32 -> all_gather(e4m3).
    Byte cost: 2 x (N/P x 1B + scales) per hop instead of 2 x N x 4B."""
    q, n, pad, = _q_flat(x)
    P = jax.lax.axis_size(axis_name)
    rows = q.data.shape[0]
    rpad = (-rows) % P
    if rpad:
        data = jnp.pad(q.data, ((0, rpad), (0, 0)))
        scale = jnp.pad(q.scale, ((0, rpad), (0, 0)), constant_values=1.0)
    else:
        data, scale = q.data, q.scale
    # reduce-scatter the fp8 payload: exchange shards, sum dequantized
    dsh = jax.lax.all_to_all(
        data.reshape(P, -1, TILE), axis_name, 0, 0, tiled=False)
    ssh = jax.lax.all_to_all(
        scale.reshape(P, -1, 1), axis_name, 0, 0, tiled=False)
    local = jnp.sum(dsh.astype(jnp.float32) * ssh, axis=0)   # f32 accumulate
    # requantize the reduced shard and all-gather it
    from repro.core.quant import quantize_rowwise as qr
    q2 = qr(local, tag="grad_compress2", kind="fused_quantize")
    gd = jax.lax.all_gather(q2.data, axis_name, axis=0, tiled=True)
    gs = jax.lax.all_gather(q2.scale, axis_name, axis=0, tiled=True)
    out = (gd.astype(jnp.float32) * gs).reshape(-1)[:rows * TILE]
    if pad:
        out = out[:n]
    return out.reshape(x.shape).astype(x.dtype)


def compress_decompress(x):
    """Round-trip quantizer for error-feedback accounting + tests."""
    q, n, pad = _q_flat(x)
    return _dq_flat(q, n, pad, x.shape, x.dtype)
