"""Transformer layer primitives: norms, RoPE, blocked (flash-style) attention
with GQA / sliding-window / logit-softcap / qk-norm, and cache-decode
attention.  Pure JAX; attention stays BF16 in every recipe (the paper's FP8
scope is the MoE/MLP stage).

Staged layer program: each decoder layer decomposes into the named stages

    attn -> router -> dispatch -> expert -> combine     (MoE layers)
    attn -> ffn                                         (dense layers)

`stage_ln_attn` below is the 'attn' stage (pre-norm + mixer + residual);
the MoE stages live in core/moe.py (decode_stage_router / _dispatch /
_expert + the combine psum/a2a) and models/lm.py drives them — either
fused inside the monolithic scan (`_run_stack`) or unrolled with a
two-layer carry window (`_run_stack_unrolled` / the streaming dist
backward) so work can be issued across layer and stage boundaries."""
from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_STAGES = ("attn", "router", "dispatch", "expert", "combine")

NEG_INF = -1e30


def rmsnorm(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(x.dtype)


def layernorm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(kind, x, p, name):
    if kind == "layernorm":
        return layernorm(x, p[f"{name}_s"], p[f"{name}_b"])
    return rmsnorm(x, p[f"{name}_s"])


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked (flash-style) attention: online softmax over KV blocks keeps the
# (S x S) logits matrix out of HBM — required for the 32k prefill shapes to
# fit the 16 GB dry-run budget.
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, q_pos, kv_pos, causal=True, window=0,
                    softcap=0.0, block_k=256, carry_sharding=None):
    """q: (B, Sq, H, hd); k,v: (B, Skv, KV, hd).  GQA via head grouping.
    window > 0 masks kv older than `window` positions behind the query.
    carry_sharding: (mesh, dp, seq_ax) — pins the online-softmax carry to the
    q sharding so the scan carry never replicates (context parallelism).

    Custom VJP: the backward pass RECOMPUTES scores block-by-block (flash
    backward) instead of letting autodiff save the (Sq x Skv) probability
    matrix — without this the 32k shapes cannot fit HBM."""
    spec = _FlashSpec(causal=causal, window=window, softcap=softcap,
                      block_k=min(block_k, k.shape[1]),
                      carry_sharding=carry_sharding)
    return _flash(spec, q, k, v, q_pos, kv_pos)


import dataclasses as _dc
from functools import partial as _partial


@_dc.dataclass(frozen=True)
class _FlashSpec:
    causal: bool
    window: int
    softcap: float
    block_k: int
    carry_sharding: object  # hashable tuple (mesh, dp, seq_ax) or None


def _mask_for(spec, q_pos, pblk, Sq, bk):
    mask = jnp.ones((Sq, bk), bool)
    if spec.causal:
        mask &= pblk[None, :] <= q_pos[:, None]
    if spec.window:
        mask &= pblk[None, :] > (q_pos[:, None] - spec.window)
    return mask


def _constrain_carry(spec, qf, m0, l0, a0):
    if spec.carry_sharding is None:
        return qf, m0, l0, a0
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh, dp, seq_ax = spec.carry_sharding
    c4 = NamedSharding(mesh, P(dp, seq_ax, None, None))
    c5 = NamedSharding(mesh, P(dp, seq_ax, None, None, None))
    return (jax.lax.with_sharding_constraint(qf, c5),
            jax.lax.with_sharding_constraint(m0, c4),
            jax.lax.with_sharding_constraint(l0, c4),
            jax.lax.with_sharding_constraint(a0, c5))


def pvary_missing(x, axes):
    """``x`` marked as varying over those manual mesh ``axes`` it does not
    vary over yet (``jax.lax.pcast`` refuses an axis already varying)."""
    vma = jax.typeof(x).vma
    missing = tuple(a for a in axes if a not in vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _vary_like(init, *refs):
    """Mark a scan's initial carry as varying over every manual mesh axis
    that ``refs`` (the body's inputs) vary over: inside ``shard_map`` the
    carry that goes in must have the type of the one that comes out."""
    vma = sorted(frozenset().union(*(jax.typeof(r).vma for r in refs)))
    return jax.tree.map(lambda t: pvary_missing(t, vma), init)


def _flash_fwd_impl(spec, q, k, v, q_pos, kv_pos):
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    # fold the softmax scale into q ONCE (saves a full-scores multiply per
    # kv block — §Perf memory-term iteration)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, hd)
    bk = spec.block_k
    nb = Skv // bk
    assert nb * bk == Skv, (Skv, bk)
    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(B, nb, bk, KV, hd), 1, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(B, nb, bk, KV, hd), 1, 0)
    pb = kv_pos.reshape(nb, bk)

    m0 = jnp.full((B, Sq, KV, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KV, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, KV, G, hd), jnp.float32)
    qf, m0, l0, a0 = _constrain_carry(spec, qf, m0, l0, a0)
    m0, l0, a0 = _vary_like((m0, l0, a0), qf, kb, vb, q_pos, pb)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, pblk = blk
        s = jnp.einsum("bqkgh,bckh->bqkgc", qf, kblk)
        if spec.softcap:
            s = spec.softcap * jnp.tanh(s / spec.softcap)
        mask = _mask_for(spec, q_pos, pblk, Sq, bk)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bqkgc,bckh->bqkgh",
                                                     p, vblk)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))              # (B,Sq,KV,G)
    o4 = out.reshape(B, Sq, H, hd).astype(q.dtype)
    if spec.carry_sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh, dp, seq_ax = spec.carry_sharding
        o4 = jax.lax.with_sharding_constraint(
            o4, NamedSharding(mesh, P(dp, seq_ax, None, None)))
    return o4, (out, lse)


@_partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(spec, q, k, v, q_pos, kv_pos):
    return _flash_fwd_impl(spec, q, k, v, q_pos, kv_pos)[0]


def _flash_fwd(spec, q, k, v, q_pos, kv_pos):
    o, (out, lse) = _flash_fwd_impl(spec, q, k, v, q_pos, kv_pos)
    return o, (q, k, v, q_pos, kv_pos, out, lse)


def _flash_bwd(spec, res, g):
    q, k, v, q_pos, kv_pos, out, lse = res
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, G, hd)
    gf = g.astype(jnp.float32).reshape(B, Sq, KV, G, hd)
    bk = spec.block_k
    nb = Skv // bk
    kb = jnp.moveaxis(k.astype(jnp.float32).reshape(B, nb, bk, KV, hd), 1, 0)
    vb = jnp.moveaxis(v.astype(jnp.float32).reshape(B, nb, bk, KV, hd), 1, 0)
    pb = kv_pos.reshape(nb, bk)
    # D_i = rowsum(g * out)
    Drow = jnp.sum(gf * out, axis=-1)                     # (B,Sq,KV,G)

    dq0 = _vary_like(jnp.zeros_like(qf), qf, gf, lse, Drow, kb, vb, q_pos, pb)

    def body(dq, blk):
        kblk, vblk, pblk = blk
        s_raw = jnp.einsum("bqkgh,bckh->bqkgc", qf, kblk)
        if spec.softcap:
            t = jnp.tanh(s_raw / spec.softcap)
            s_capped = spec.softcap * t
        else:
            s_capped = s_raw
        mask = _mask_for(spec, q_pos, pblk, Sq, bk)
        s_m = jnp.where(mask[None, :, None, None, :], s_capped, NEG_INF)
        p = jnp.exp(s_m - lse[..., None])                 # (B,Sq,KV,G,c)
        dv = jnp.einsum("bqkgc,bqkgh->bckh", p, gf)
        dp = jnp.einsum("bqkgh,bckh->bqkgc", gf, vblk)
        ds = p * (dp - Drow[..., None])                   # d s_capped
        if spec.softcap:
            ds = ds * (1.0 - t * t)                       # through tanh
        # scale folded into qf: dq needs ds*scale@k (applied at the end),
        # dk needs ds@(q*scale) = ds@qf directly
        dq_blk = jnp.einsum("bqkgc,bckh->bqkgh", ds, kblk)
        dk = jnp.einsum("bqkgc,bqkgh->bckh", ds, qf)
        return dq + dq_blk, (dk, dv)

    dq, (dk_s, dv_s) = jax.lax.scan(body, dq0, (kb, vb, pb))
    dq = dq * scale        # complete d(q*scale)/dq
    dk = jnp.moveaxis(dk_s, 0, 1).reshape(B, Skv, KV, hd)
    dv = jnp.moveaxis(dv_s, 0, 1).reshape(B, Skv, KV, hd)
    return (dq.reshape(B, Sq, H, hd).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype), None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def decode_attention(q, k_cache, v_cache, *, pos, window=0, softcap=0.0):
    """Single-step decode: q (B, 1, H, hd); caches (B, Smax, KV, hd).
    pos: current position — scalar (shared phase, the fixed-batch bench path)
    OR a (B,) vector (continuous batching: each request at its own depth);
    kv rows [0, pos_b] are valid.
    For windowed layers with a SCALAR pos only the last `window` cache rows
    are read (dynamic_slice) — the local-attention memory saving is real; the
    per-request path reads the full cache and window-masks (starts differ
    per row, so a shared slice does not exist)."""
    B, _, H, hd = q.shape
    _, Smax, KV, _ = k_cache.shape
    pos = jnp.asarray(pos)
    per_request = pos.ndim == 1
    if window and window < Smax and not per_request:
        start = jnp.clip(pos - window + 1, 0, Smax - window)
        k_r = jax.lax.dynamic_slice_in_dim(k_cache, start, window, axis=1)
        v_r = jax.lax.dynamic_slice_in_dim(v_cache, start, window, axis=1)
        kv_pos = start + jnp.arange(window)
    else:
        k_r, v_r = k_cache, v_cache
        kv_pos = jnp.arange(Smax)
    G = H // KV
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    qf = q.astype(jnp.float32).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bckh->bkgc", qf, k_r.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    if per_request:
        mask = kv_pos[None, :] <= pos[:, None]          # (B, c)
        if window:
            mask &= kv_pos[None, :] > (pos[:, None] - window)
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    else:
        s = jnp.where((kv_pos <= pos)[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgc,bckh->bkgh", p, v_r.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + attention [+ cache update]).
# ---------------------------------------------------------------------------
def _cp_constrain(plan, q, k, v):
    """Sequence-parallel (context-parallel) attention sharding: queries stay
    seq-sharded over the model axis (matching the residual-stream SP), keys/
    values are gathered — uniform across head counts (DESIGN.md §4).
    Returns (q, k, v, carry_sharding) for flash_attention."""
    if plan is None or plan.mesh is None:
        return q, k, v, None
    import numpy as _np
    from jax.sharding import NamedSharding, PartitionSpec as P
    tp = plan.mesh.shape[plan.tp_axis]
    B, S = q.shape[0], q.shape[1]
    dp_size = int(_np.prod([plan.mesh.shape[a] for a in plan.dp_axes])) \
        if plan.dp_axes else 1
    dp = plan.dp_axes if B % max(1, dp_size) == 0 else None
    seq_ax = plan.tp_axis if S % tp == 0 else None
    q = jax.lax.with_sharding_constraint(
        q, NamedSharding(plan.mesh, P(dp, seq_ax, None, None)))
    k = jax.lax.with_sharding_constraint(
        k, NamedSharding(plan.mesh, P(dp, None, None, None)))
    v = jax.lax.with_sharding_constraint(
        v, NamedSharding(plan.mesh, P(dp, None, None, None)))
    return q, k, v, (plan.mesh, dp, seq_ax)


def project_qkv(cfg, p, x, positions, cross_kv=None):
    """QKV projections + bias + qk-norm + RoPE (shared by the train/prefill,
    dense-cache decode, and paged decode paths).
    x: (B, S, D); positions: (S,) or (B, S).  Returns q (B,S,H,hd) and
    k, v (B,S,KV,hd) (or the passed-through cross_kv)."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = jnp.einsum("bsd,dn->bsn", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    if cross_kv is None:
        k = jnp.einsum("bsd,dn->bsn", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dn->bsn", x, p["wv"].astype(x.dtype))
        if cfg.qkv_bias:
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        k = k.reshape(B, S, KV, hd)
        v = v.reshape(B, S, KV, hd)
    else:
        k, v = cross_kv

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"]) if cross_kv is None else k
    if cross_kv is None and cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def stage_ln_attn(cfg, p, x, *, positions, layer_window=0, cache=None,
                  cache_pos=None, causal=True, plan=None):
    """Named stage 'attn' for pure-attention layer kinds: pre-norm +
    attention + residual add.  Returns (x + attn_out, new_cache).  The
    stage output is checkpoint_name-tagged so the MemoryPlan 'full' policy
    (train/memory.py) can keep the bf16 stage boundary resident."""
    from repro.core.quant import tag_saveable
    from repro.obs.trace import stage_annotation
    with stage_annotation("attn"):
        h = apply_norm(cfg.norm, x, p, "ln1")
        out, new_cache = attn_block(cfg, p, h, positions=positions,
                                    layer_window=layer_window, cache=cache,
                                    cache_pos=cache_pos, causal=causal,
                                    plan=plan)
        return tag_saveable(x + out, "stage_attn_out"), new_cache


def attn_block(cfg, p, x, *, positions, layer_window=0, cache=None,
               cache_pos=None, cross_kv=None, causal=True, plan=None):
    """cfg: ArchConfig; p: layer param dict; x: (B, S, D).
    cache: optional (k_cache, v_cache) for decode; cache_pos scalar (shared
    phase) or (B,) per-request; cross_kv: (k, v) already projected encoder
    states for cross-attention."""
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q, k, v = project_qkv(cfg, p, x, positions, cross_kv=cross_kv)

    carry_sharding = None
    if cache is None:
        q, k, v, carry_sharding = _cp_constrain(plan, q, k, v)

    if cache is not None:
        k_cache, v_cache = cache
        if cross_kv is None:
            if jnp.ndim(cache_pos) == 1:   # per-request write rows
                rows = jnp.arange(B)
                k_cache = k_cache.at[rows, cache_pos].set(
                    k[:, 0].astype(k_cache.dtype))
                v_cache = v_cache.at[rows, cache_pos].set(
                    v[:, 0].astype(v_cache.dtype))
            else:
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.astype(k_cache.dtype), cache_pos, axis=1)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.astype(v_cache.dtype), cache_pos, axis=1)
        o = decode_attention(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                             pos=cache_pos, window=layer_window,
                             softcap=cfg.attn_softcap)
        new_cache = (k_cache, v_cache)
    else:
        is_causal = causal and cross_kv is None
        kv_pos = positions if cross_kv is None else \
            jnp.arange(k.shape[1], dtype=positions.dtype)
        o = flash_attention(q, k, v, q_pos=positions, kv_pos=kv_pos,
                            causal=is_causal, window=layer_window,
                            softcap=cfg.attn_softcap,
                            carry_sharding=carry_sharding)
        new_cache = None
    out = jnp.einsum("bsn,nd->bsd", o.reshape(B, S, H * hd),
                     p["wo"].astype(x.dtype))
    return out, new_cache
