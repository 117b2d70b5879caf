"""Seeded weights for a configuration, in the layout the program takes.

The benchmark makes the weights, not the program: the same seed gives the
same values to the program under test and to the plain reference, and the
reference never reads anything the program made.  Every leaf is drawn from
its own key, ``fold_in(seed_key, leaf_index)``, at its published (live)
shape and then zero-padded to the shape the program runs (the dense FFN's
10944 columns padded to 11008), so the reference regenerates any leaf alone.

The tree is ``default_leaves`` unless the configuration's reference module
(``bench/reference/<reference>.py``, under the root the cell was resolved
from) defines ``leaves(cfg, padded)``.  Such a list may add leaves or
replace some of the default ones; it puts the leaves it adds after the
default ones, so those keep their indices and their draws.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from pathlib import Path
from typing import Tuple

import jax
import jax.numpy as jnp

from bench import counts, harness


@dataclasses.dataclass(frozen=True)
class Leaf:
    path: Tuple[str, ...]
    shape: Tuple[int, ...]          # as the program holds it
    live: Tuple[int, ...]           # as drawn (<= shape, zero beyond)
    dtype: str
    std: float                      # 0: zeros


def seed_key(seed: int):
    """A key from any non-negative seed below 2**62 (wider than int32)."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def leaves(cfg: dict, padded: bool = True):
    """Leaf specs of the program's parameter tree, in a fixed order: the
    configuration's reference's own list where it has one."""
    root = cfg.get(harness.ROOT_KEY, str(harness.ROOT))
    own = _reference_leaves(root, cfg["reference"])
    return (own or default_leaves)(cfg, padded)


@functools.lru_cache(maxsize=None)
def _reference_leaves(root: str, name: str):
    return getattr(harness.reference(Path(root), {"reference": name}),
                   "leaves", None)


def default_leaves(cfg: dict, padded: bool = True):
    """DeepSeek-V2's tree with GQA attention.  The router scores every
    published routed expert; the expert stacks hold the experts held here
    (``counts.routed_experts``)."""
    a = cfg["arch"]
    D, H, hd = cfg["hidden_size"], cfg["num_attention_heads"], a["head_dim"]
    KV = cfg["num_key_value_heads"]
    V = cfg["vocab_size"]
    F_live = cfg["intermediate_size"]
    F = a["d_ff"] if padded else F_live
    E, held = counts.routed_experts(cfg)
    Eh, Fe = len(held), cfg["moe_intermediate_size"]
    Fs = cfg["n_shared_experts"] * Fe
    nd = cfg["first_k_dense_replace"]
    nm = cfg["num_hidden_layers"] - nd
    std = cfg["init_std"]
    out_std = std / math.sqrt(cfg["published"]["num_hidden_layers"])
    bf, f32 = "bfloat16", "float32"
    out = [Leaf(("embed",), (V, D), (V, D), bf, std),
           Leaf(("final_norm_s",), (D,), (D,), f32, 0.0),
           Leaf(("lm_head",), (D, V), (D, V), bf, std)]

    def attn(stack, n):
        return [Leaf((stack, "ln1_s"), (n, D), (n, D), f32, 0.0),
                Leaf((stack, "ln2_s"), (n, D), (n, D), f32, 0.0),
                Leaf((stack, "wq"), (n, D, H * hd), (n, D, H * hd), bf, std),
                Leaf((stack, "wk"), (n, D, KV * hd), (n, D, KV * hd), bf, std),
                Leaf((stack, "wv"), (n, D, KV * hd), (n, D, KV * hd), bf, std),
                Leaf((stack, "wo"), (n, H * hd, D), (n, H * hd, D), bf,
                     out_std)]

    if nd:
        out += attn("dense_layers", nd)
        out += [Leaf(("dense_layers", "w13"), (nd, D, 2, F),
                     (nd, D, 2, F_live), bf, std),
                Leaf(("dense_layers", "w2"), (nd, F, D), (nd, F_live, D), bf,
                     out_std)]
    out += attn("layers", nm)
    out += [Leaf(("layers", "w_router"), (nm, D, E), (nm, D, E), f32, std),
            Leaf(("layers", "we13"), (nm, Eh, D, 2, Fe), (nm, Eh, D, 2, Fe),
                 bf, std),
            Leaf(("layers", "we2"), (nm, Eh, Fe, D), (nm, Eh, Fe, D), bf,
                 out_std),
            Leaf(("layers", "ws13"), (nm, D, 2, Fs), (nm, D, 2, Fs), bf, std),
            Leaf(("layers", "ws2"), (nm, Fs, D), (nm, Fs, D), bf, out_std)]
    return out


def draw(leaf: Leaf, key, index: int, dtype=None):
    """One leaf: N(0, std^2) at its live shape, zero-padded to its shape."""
    dt = jnp.dtype(dtype or leaf.dtype)
    if leaf.std == 0.0:
        return jnp.zeros(leaf.shape, dt)
    v = jax.random.normal(jax.random.fold_in(key, index), leaf.live,
                          jnp.float32) * leaf.std
    v = v.astype(leaf.dtype).astype(dt)        # the served values, exactly
    pad = [(0, s - l) for s, l in zip(leaf.shape, leaf.live)]
    return jnp.pad(v, pad) if any(p[1] for p in pad) else v


def nest(pairs):
    tree = {}
    for path, v in pairs:
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = v
    return tree


def make_tree(cfg: dict, key, padded: bool = True, dtype=None):
    """The whole tree (call inside jit: one program draws every leaf)."""
    specs = leaves(cfg, padded)
    return nest((lf.path, draw(lf, key, i, dtype)) for i, lf in enumerate(specs))


def leaf_index(cfg: dict, path) -> int:
    return [lf.path for lf in leaves(cfg)].index(tuple(path))
