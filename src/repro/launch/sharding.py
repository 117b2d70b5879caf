"""Sharding plans: arch -> ParallelPlan + pytree PartitionSpecs.

Rules (DESIGN.md §4):
  TP ('model')    attention heads / d_ff / vocab, when divisible
  EP ('model')    MoE experts when n_experts % tp == 0 (else TP-in-expert)
  DP ('data' [+ 'pod'])  batch/tokens; optimizer state ZeRO-1 over 'data'
  FSDP ('data')   d_model dim of the huge expert/MLP weights (>=200B archs),
                  gathered inside the shard_map blocks
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.lm import ParallelPlan, mlp_tp_ok


def make_plan(cfg: ArchConfig, mesh) -> ParallelPlan:
    import os
    axes = mesh.axis_names
    dp_axes = tuple(a for a in ("pod", "data") if a in axes)
    tp = mesh.shape["model"]
    moe_mode = "ep" if (cfg.moe and cfg.n_experts % tp == 0
                        and cfg.n_experts >= tp) else "tp"
    return ParallelPlan(
        mesh=mesh, dp_axes=dp_axes, tp_axis="model", moe_mode=moe_mode,
        fsdp_axis="data" if cfg.fsdp else None,
        shard_map_mlp=True,
        moe_tp_combine=os.environ.get("REPRO_MOE_TP_COMBINE", "local_first"),
        mlp_tp=os.environ.get("REPRO_MLP_TP", "0") == "1",
    )


def _tp_ok(n, tp):
    return n % tp == 0


def _attn_param_bytes(cfg: ArchConfig) -> int:
    hd = cfg.head_dim
    per = cfg.d_model * (cfg.n_heads + 2 * cfg.n_kv) * hd \
        + cfg.n_heads * hd * cfg.d_model
    return cfg.n_layers * per * 2


def param_specs(cfg: ArchConfig, mesh) -> Any:
    """PartitionSpec pytree matching init_params' structure (by leaf path)."""
    tp = mesh.shape["model"]
    fs = "data" if cfg.fsdp else None
    # attention weights: replicate when small (<1 GiB total — psum'd Wgrad is
    # cheaper than per-layer gathers), else FSDP over data x model jointly
    attn_shard = _attn_param_bytes(cfg) > 2 ** 30
    emb_tp = _tp_ok(cfg.vocab_padded, tp)

    def leaf_spec(path: str, ndim: int, w8: bool = False) -> P:
        # `path` is the dotted key path WITHOUT the stacking dim; specs below
        # are written for the stacked array (leading None for the layer dim
        # when ndim exceeds the per-layer rank).  w8: a field of a W8-resident
        # QTensor leaf (serve/w8.py), whose `we13` is stored (E, D, g*Fe).
        name = path.split(".")[-1]
        rank = 3 if w8 and name == "we13" else _per_layer_rank(name)
        lead = (None,) * (ndim - rank)

        def spec(*s):
            return P(*(lead + s))

        if name == "embed":
            return P("model" if emb_tp else None, None)
        if name == "lm_head":
            return P(None, "model" if emb_tp else None)
        # attention is sequence-parallel (CP) — heads never TP-shard; the
        # projection weights FSDP over data x model jointly (gathered per
        # layer inside the scan) when big, replicated when small
        if name in ("wq", "wk", "wv", "wo"):
            return spec(("data", "model") if attn_shard else None, None)
        # dense MLP weights: DP-mode baseline (no TP) — replicated when
        # small, FSDP over 'data' when cfg.fsdp (gathered inside shard_map)
        if name == "w13":                     # (D, g, F)
            return spec(fs, None, None)
        if name == "w2":                      # (F, D)
            return spec(None, fs)
        if name == "ws13":                    # shared expert (D, g, Fs)
            return spec(fs, None, None)
        if name == "ws2":
            return spec(None, fs)
        if name == "we13" and w8:             # (E, D, g*Fe)
            ep = cfg.n_experts % tp == 0 and cfg.n_experts >= tp
            return spec("model" if ep else None, fs, None)
        if name == "we13":                    # (E, D, g, Fe)
            if cfg.n_experts % tp == 0 and cfg.n_experts >= tp:
                return spec("model", fs, None, None)          # EP
            return spec(None, fs, None, "model")              # TP-in-expert
        if name == "we2":                     # (E, Fe, D)
            if cfg.n_experts % tp == 0 and cfg.n_experts >= tp:
                return spec("model", None, fs)
            return spec(None, "model", fs)
        if name == "in_proj":                 # mamba (D, k) — replicated TP
            return spec("data" if fs else None, None)
        if name == "out_proj":
            return spec(None, "data" if fs else None)
        return P(*((None,) * ndim))           # norms, biases, router, conv

    return leaf_spec


_PER_LAYER_RANK = {
    "embed": 2, "lm_head": 2,
    "wq": 2, "wk": 2, "wv": 2, "wo": 2,
    "w13": 3, "w2": 2, "ws13": 3, "ws2": 2,
    "we13": 4, "we2": 3,
    "in_proj": 2, "out_proj": 2,
}


def _per_layer_rank(name):
    return _PER_LAYER_RANK.get(name, 0)


def tree_specs(cfg: ArchConfig, mesh, tree_shapes) -> Any:
    """Build the full PartitionSpec pytree for a params-shaped tree."""
    ls = param_specs(cfg, mesh)

    def to_spec(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        # QTensor leaves ('we13.data' / 'we13.scale') follow the parent rule
        w8 = len(keys) > 1 and str(keys[-1]) in ("data", "scale")
        if w8:
            keys = keys[:-1]
        name = ".".join(str(k) for k in keys)
        ndim = len(leaf.shape)
        base = ls(name, ndim, w8)
        if len(base) < ndim:
            base = P(*(tuple(base) + (None,) * (ndim - len(base))))
        if len(base) > ndim:
            base = P(*tuple(base)[:ndim])
        # drop shardings that don't divide the dim evenly
        fixed = []
        for dim, ax in zip(leaf.shape, tuple(base)):
            if ax is None:
                fixed.append(None)
                continue
            size = mesh.shape[ax] if isinstance(ax, str) else \
                int(jnp.prod(jnp.array([mesh.shape[a] for a in ax])))
            fixed.append(ax if dim % size == 0 else None)
        return NamedSharding(mesh, P(*fixed))

    return jax.tree_util.tree_map_with_path(to_spec, tree_shapes)


def opt_state_specs(cfg: ArchConfig, mesh, params_specs, opt_shapes) -> Any:
    """ZeRO-1: moments/master additionally sharded over 'data' on the first
    dim that divides evenly and is not already sharded.  Tolerates QTensor-
    encoded state leaves (AdamWConfig.state_policy): their flat (rows, TILE)
    payload+scale pair shards over 'data' on the row dim."""
    dsize = mesh.shape["data"]

    def zero1(sharding, leaf):
        spec = (list(sharding.spec)
                + [None] * (len(leaf.shape) - len(sharding.spec)))
        spec = spec[:len(leaf.shape)]   # QTensor flat leaves drop param rank
        if any(s == "data" or (isinstance(s, tuple) and "data" in s)
               for s in spec):
            return NamedSharding(mesh, P(*spec))
        for i, (dim, s) in enumerate(zip(leaf.shape, spec)):
            if s is None and dim % dsize == 0 and dim >= dsize:
                spec[i] = "data"
                break
        return NamedSharding(mesh, P(*spec))

    def build(path, leaf):
        # path like ('m'|'v'|'master', <params path...>[, 'data'|'scale'])
        # or ('step',)
        if not path or getattr(path[0], "key", None) == "step":
            return NamedSharding(mesh, P())
        sub_path = path[1:]
        ps = params_specs
        qtensor_attr = False
        for k in sub_path:
            if not isinstance(ps, (dict, list, tuple)):
                qtensor_attr = True   # rest of the path is QTensor attrs
                break
            key = getattr(k, "key", getattr(k, "idx", None))
            ps = ps[key]
        if qtensor_attr:
            # flat (rows, TILE) payload/scale pair: the param's spec does
            # not apply to these dims — zero1 row-shard both consistently
            ps = NamedSharding(mesh, P())
        return zero1(ps, leaf)

    return jax.tree_util.tree_map_with_path(build, opt_shapes)


def dist_state_specs(mesh, opt_state, axis: str = "data") -> Any:
    """NamedShardings for a DistPlan optimizer state (repro.dist): the flat
    ZeRO-1 bucket arrays — e4m3/f16 payloads AND their po2 row scales —
    shard over the DP axis on the row dim (scale-aware: slicing rows slices
    payload and scales consistently); 'step' and the sensitive-leaf state
    stay replicated.  Pass to checkpointing.restore to re-shard a ZeRO-1
    checkpoint onto a different DP mesh size."""
    dsize = mesh.shape[axis]

    def spec(path, leaf):
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        nd = getattr(leaf, "ndim", 0)
        if "flat" in keys and nd >= 1 and leaf.shape[0] % dsize == 0 \
                and leaf.shape[0] >= dsize:
            return NamedSharding(mesh, P(axis, *([None] * (nd - 1))))
        return NamedSharding(mesh, P(*([None] * nd)))

    return jax.tree_util.tree_map_with_path(spec, opt_state)


def _axes_size(mesh, ax):
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        out = 1
        for a in ax:
            out *= mesh.shape[a]
        return out
    return mesh.shape[ax]


def _fit(mesh, spec: P, shape) -> NamedSharding:
    """Drop partitions that don't divide the dim (e.g. batch 1 over dp)."""
    spec = list(spec) + [None] * (len(shape) - len(spec))
    fixed = []
    for dim, ax in zip(shape, spec):
        fixed.append(ax if ax is not None and dim % _axes_size(mesh, ax) == 0
                     else None)
    return NamedSharding(mesh, P(*fixed))


def batch_specs(mesh, batch_shapes, dp_axes) -> Any:
    def spec(path, leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return NamedSharding(mesh, P())
        # grad-accum leading dim is unsharded: batch dim is dim0 for ndim<=3
        # ({tokens,targets,mask}: (B,S) / (A,B,S); prefix: (B,P,D))
        keys = [getattr(k, "key", "") for k in path]
        name = keys[-1] if keys else ""
        if name in ("tokens", "targets", "mask"):
            sp = P(None, dp_axes, None) if nd == 3 else P(dp_axes, None)
            return _fit(mesh, sp, leaf.shape)
        if name in ("prefix", "enc_input"):
            sp = P(None, dp_axes, None, None) if nd == 4 \
                else P(dp_axes, None, None)
            return _fit(mesh, sp, leaf.shape)
        return _fit(mesh, P(*([dp_axes] + [None] * (nd - 1))), leaf.shape)

    return jax.tree_util.tree_map_with_path(spec, batch_shapes)


def cache_specs(cfg: ArchConfig, mesh, cache_shapes, dp_axes) -> Any:
    """KV caches: batch over dp; heads over model if divisible, else head_dim
    over model (dense-GQA kv counts are small); SSM state heads over model."""
    tp = mesh.shape["model"]

    def spec(path, leaf):
        keys = [getattr(k, "key", "") for k in path]
        name = keys[-1]
        nd = len(leaf.shape)
        if name in ("k", "v"):               # (L, B, S, KV, hd)
            kv, hd = leaf.shape[3], leaf.shape[4]
            if kv % tp == 0:
                sp = P(None, dp_axes, None, "model", None)
            elif hd % tp == 0:
                sp = P(None, dp_axes, None, None, "model")
            else:
                sp = P(None, dp_axes, None, None, None)
            return _fit(mesh, sp, leaf.shape)
        if name == "state":                  # (L, B, H, P, N)
            h = leaf.shape[2]
            sp = P(None, dp_axes, "model" if h % tp == 0 else None,
                   None, None)
            return _fit(mesh, sp, leaf.shape)
        if name == "conv":                   # (L, B, conv-1, ch)
            return _fit(mesh, P(None, dp_axes, None, None), leaf.shape)
        return NamedSharding(mesh, P(*([None] * nd)))

    return jax.tree_util.tree_map_with_path(spec, cache_shapes)
