"""W8-resident serving: pre-quantized FP8 expert/MLP weights.

Extends the paper's FP8-weight format (blockwise po2 scales — the exact
layout the training GEMMs consume) to SERVING residency: instead of
FSDP-sharded BF16 weights gathered per layer (the collective-bound decode
baseline, EXPERIMENTS.md §Perf cell 3), the big weights live on-chip as
e4m3 payload + po2 scales — half the bytes, zero gather traffic, and the
grouped GEMM consumes them directly (weights are quantized ONCE here, not
per step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.fp8 import TILE
from repro.core.quant import QTensor, quantize

# param leaves converted to resident FP8 for serving (the big matmul weights;
# norms/router/biases stay f32, attention projections stay bf16 — they are
# small and latency-critical)
_W8_LEAVES = {
    "we13": (1, 1, TILE, 1, TILE),   # (L, E, D, g, Fe), stored (L, E, D, g*Fe)
    "we2": (1, 1, TILE, TILE),       # (L, E, Fe, D)
}


def _pad_ok(shape, tile):
    return all(n % t == 0 for n, t in zip(shape, tile))


def quantize_params_for_serving(params):
    """Replace the big matmul weights with blockwise-po2 QTensors."""
    def conv(path, leaf):
        keys = [getattr(k, "key", "") for k in path]
        name = keys[-1]
        if name in _W8_LEAVES:
            tile = _W8_LEAVES[name]
            if len(tile) == leaf.ndim and _pad_ok(leaf.shape, tile):
                q = quantize(leaf, tile, tag=f"q_w8_{name}",
                             kind="fused_quantize")
                return _merge_gate(q) if name == "we13" else q
        return leaf

    return jax.tree_util.tree_map_with_path(conv, params)


def is_w8(p) -> bool:
    return isinstance(p, QTensor)


def _merge_gate(q: QTensor) -> QTensor:
    """(L, E, D, g, Fe) blockwise QTensor -> (L, E, D, g*Fe), the layout the
    grouped GEMM reads.  Done once here: a TPU tiles the last two axes in
    memory, so merging g and Fe in the step copies the whole payload on
    every pass.  Exact block relabeling (Fe is whole tiles, so each block
    lies in one of the gate/up halves)."""
    *lead, g, fe = q.data.shape
    return QTensor(data=q.data.reshape(*lead, g * fe),
                   scale=q.scale.reshape(*q.scale.shape[:-2], -1),
                   tile=(1, 1, TILE, TILE))


def retile(q: QTensor, tile) -> QTensor:
    """Fix up the static tile metadata after tree-level slicing."""
    return QTensor(data=q.data, scale=q.scale, tile=tuple(tile))
