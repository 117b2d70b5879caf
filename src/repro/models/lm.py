"""Unified decoder-LM trunk covering all assigned architecture families:
dense GQA (starcoder2/qwen/llava), local:global patterns (gemma2/3), SSM
(mamba2), hybrid (hymba), MoE (qwen3-moe/grok/deepseek), encoder-decoder
(seamless), with VLM/audio stub frontends.

Layer parameters are stacked (L, ...) and scanned in pattern groups;
rematerialization is owned by the MemoryPlan (train/memory.py), which wraps
each group/layer per cfg.remat_policy.  The MLP/MoE stage runs inside
shard_map so the paper's
FP8 dispatch/dataflow recipes apply uniformly (core/moe.py, core/linear.py);
attention/norm/embedding run under pjit auto-sharding in BF16.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import quant as quant_stats
from repro.core.linear import dense_mlp, expert_ffn, quantize_entry
from repro.core.moe import (DispatchPlan, MoEConfig, moe_block,
                            moe_block_decode, moe_block_decode_overlapped,
                            moe_block_overlapped, moe_block_tp)
from repro.core.recipes import Recipe
from repro.models.layers import (apply_norm, attn_block, pvary_missing,
                                 stage_ln_attn)
from repro.models.ssm import mamba2_block
from repro.train.memory import MemoryPlan


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """How this run maps onto the mesh (None mesh = single-process tests)."""
    mesh: object = None
    dp_axes: tuple = ()            # axes sharding the batch/tokens
    tp_axis: str = "model"
    moe_mode: str = "ep"           # 'ep' (E >= tp) or 'tp' (E < tp)
    fsdp_axis: Optional[str] = None  # gather MoE/MLP weights over this axis
    shard_map_mlp: bool = True     # run dense MLP through shard_map (train)
    mlp_tp: bool = False           # TP-shard d_ff (psum combine) instead of
                                   # DP-over-all-axes; DP wins when the
                                   # activation psum volume > weight traffic
    moe_tp_combine: str = "local_first"  # TP-MoE combine ordering (§Perf):
                                   # 'psum_first' | 'local_first' |
                                   # 'reduce_scatter'
    moe_overlap: Optional[DispatchPlan] = None  # chunked/overlapped EP
                                   # dispatch pipeline (core/moe.py): when
                                   # set, train/prefill EP MoE layers run
                                   # moe_block_overlapped and the shared
                                   # expert is issued BEFORE the dispatch so
                                   # its GEMMs overlap the first chunk's
                                   # fused all-to-all; decode-path MoE layers
                                   # run moe_block_decode_overlapped (the
                                   # chunk-pipelined combine psum)
    stage_layers: bool = False     # run the decoder stacks through the
                                   # UNROLLED staged layer program
                                   # (_run_stack_unrolled) instead of the
                                   # monolithic lax.scan: per-layer trace
                                   # regions with a two-layer carry window —
                                   # what the streaming DP wire's backward
                                   # consumes (repro.dist schedule='stream')

    @property
    def token_axes_moe(self):      # EP: tokens also sharded over tp (SP)
        return self.dp_axes + (self.tp_axis,)


NO_PLAN = ParallelPlan(mesh=None, dp_axes=(), shard_map_mlp=False)

# weight of the summed router aux losses in the training loss — shared by
# forward() and the staged backward (train_step._streamed_grads), which
# feeds it in as each layer's aux cotangent
AUX_LOSS_COEF = 0.01


# ---------------------------------------------------------------------------
# Parameter initialization (stacked layers).  For the dry-run this is only
# ever called under jax.eval_shape — no memory is allocated.
# ---------------------------------------------------------------------------
def _dense_init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _layer_params(cfg: ArchConfig, key, kind: str, moe_layer: bool, dtype):
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    g = cfg.gate_factor
    ks = jax.random.split(key, 24)
    p = {}
    sc = 0.02

    def norm_params(i, name):
        p[f"{name}_s"] = jnp.zeros((D,), jnp.float32)
        if cfg.norm == "layernorm":
            p[f"{name}_s"] = jnp.ones((D,), jnp.float32)
            p[f"{name}_b"] = jnp.zeros((D,), jnp.float32)

    norm_params(0, "ln1")
    norm_params(1, "ln2")

    if kind in ("global", "local", "hybrid"):
        p["wq"] = _dense_init(ks[0], (D, H * hd), sc, dtype)
        p["wk"] = _dense_init(ks[1], (D, KV * hd), sc, dtype)
        p["wv"] = _dense_init(ks[2], (D, KV * hd), sc, dtype)
        p["wo"] = _dense_init(ks[3], (H * hd, D), sc / cfg.n_layers**0.5, dtype)
        if cfg.qkv_bias:
            p["bq"] = jnp.zeros((H * hd,), jnp.float32)
            p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
            p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
        if cfg.qk_norm:
            p["q_norm"] = jnp.zeros((hd,), jnp.float32)
            p["k_norm"] = jnp.zeros((hd,), jnp.float32)

    if kind in ("ssm", "hybrid") and cfg.ssm_state:
        di, N, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        p["in_proj"] = _dense_init(ks[4], (D, 2 * di + 2 * N + nh), sc, dtype)
        p["conv_w"] = _dense_init(ks[5], (cfg.ssm_conv, di + 2 * N), 0.2,
                                  jnp.float32)
        p["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32))
        p["D"] = jnp.ones((nh,), jnp.float32)
        p["dt_bias"] = jnp.zeros((nh,), jnp.float32)
        p["norm_s"] = jnp.zeros((di,), jnp.float32)
        p["out_proj"] = _dense_init(ks[6], (di, D), sc / cfg.n_layers**0.5,
                                    dtype)

    if moe_layer:
        E, Fe = cfg.n_experts, cfg.d_ff_expert
        p["w_router"] = _dense_init(ks[7], (D, E), sc, jnp.float32)
        p["we13"] = _dense_init(ks[8], (E, D, g, Fe), sc, dtype)
        p["we2"] = _dense_init(ks[9], (E, Fe, D), sc / cfg.n_layers**0.5, dtype)
        if cfg.n_shared_experts:
            Fs = cfg.n_shared_experts * Fe
            p["ws13"] = _dense_init(ks[10], (D, g, Fs), sc, dtype)
            p["ws2"] = _dense_init(ks[11], (Fs, D), sc / cfg.n_layers**0.5,
                                   dtype)
    elif cfg.d_ff and kind != "ssm":
        p["w13"] = _dense_init(ks[12], (D, g, cfg.d_ff), sc, dtype)
        p["w2"] = _dense_init(ks[13], (cfg.d_ff, D), sc / cfg.n_layers**0.5,
                              dtype)
    return p


def _stack_layers(cfg, key, layer_ids, kinds, moe_flags, dtype):
    """Build per-layer params and stack along dim 0 (for lax.scan)."""
    keys = jax.random.split(key, max(len(layer_ids), 1))

    def one(i):
        li = layer_ids[i]
        return _layer_params(cfg, keys[i], kinds[li % len(kinds)] if False
                             else kinds[i], moe_flags[i], dtype)

    trees = [one(i) for i in range(len(layer_ids))]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def layer_kinds(cfg: ArchConfig):
    return [cfg.pattern[i % len(cfg.pattern)] for i in range(cfg.n_layers)]


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16):
    kq = jax.random.split(key, 8)
    Vp, D = cfg.vocab_padded, cfg.d_model
    params = {
        "embed": _dense_init(kq[0], (Vp, D), 0.02, dtype),
        "final_norm_s": (jnp.ones if cfg.norm == "layernorm" else jnp.zeros)(
            (D,), jnp.float32),
    }
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((D,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(kq[1], (D, Vp), 0.02, dtype)

    kinds = layer_kinds(cfg)
    nd = cfg.n_dense_layers if cfg.moe else 0
    if nd:
        params["dense_layers"] = _stack_layers(
            cfg, kq[2], list(range(nd)), kinds[:nd], [False] * nd, dtype)
    main_ids = list(range(nd, cfg.n_layers))
    params["layers"] = _stack_layers(
        cfg, kq[3], main_ids, kinds[nd:], [cfg.moe] * len(main_ids), dtype)

    if cfg.encdec:
        enc_kinds = ["global"] * cfg.n_enc_layers
        params["enc_layers"] = _stack_layers(
            cfg, kq[4], list(range(cfg.n_enc_layers)), enc_kinds,
            [False] * cfg.n_enc_layers, dtype)
        # decoder cross-attention params (stacked over decoder layers)
        def cross(i, k):
            hd, H, KV = cfg.head_dim, cfg.n_heads, cfg.n_kv
            return {
                "wq": _dense_init(k, (D, H * hd), 0.02, dtype),
                "wk": _dense_init(k, (D, KV * hd), 0.02, dtype),
                "wv": _dense_init(k, (D, KV * hd), 0.02, dtype),
                "wo": _dense_init(k, (H * hd, D), 0.02, dtype),
                "ln_s": jnp.zeros((D,), jnp.float32),
            }
        ck = jax.random.split(kq[5], cfg.n_layers)
        trees = [cross(i, ck[i]) for i in range(cfg.n_layers)]
        params["cross_layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    return params


# ---------------------------------------------------------------------------
# MLP / MoE stage dispatch (shard_map around the recipe pathways).
# ---------------------------------------------------------------------------
def mlp_tp_ok(F: int, tp: int) -> bool:
    """F can TP-shard over `tp` only if the shard stays 128-tile aligned
    (the FP8 transpose/quant block constraint)."""
    return F % tp == 0 and (F // tp) % 128 == 0


def _mlp_stage(cfg, recipe, plan, p, x):
    """Dense MLP.  x: (B, S, D) -> (B, S, D).

    Two sharded modes:
      TP  — d_ff over the model axis, tokens over dp, psum combine
            (requires (d_ff/tp) % 128 == 0 for the FP8 tile constraint);
      DP  — weights replicated on the model axis, tokens sharded over
            dp + model (no redundant compute, Wgrad psums over all axes).
    """
    B, S, D = x.shape
    g = cfg.gate_factor
    w13, w2 = p["w13"], p["w2"]
    F = w13.shape[-1]
    if not plan.shard_map_mlp or plan.mesh is None:
        y = dense_mlp(recipe, cfg.act, x.reshape(B * S, D),
                      w13.reshape(D, g * F), w2)
        return y.reshape(B, S, D)

    tp_size = plan.mesh.shape[plan.tp_axis]
    use_tp = plan.mlp_tp and mlp_tp_ok(F, tp_size)
    gather = plan.fsdp_axis
    armed = quant_stats.stats_armed()
    all_axes = tuple(plan.mesh.axis_names)

    def body(x3, w13_l, w2_l):
        if gather:
            w13_l = jax.lax.all_gather(w13_l, gather, axis=0, tiled=True)
            w2_l = jax.lax.all_gather(w2_l, gather, axis=1, tiled=True)
        Dl, gl, Fl = w13_l.shape
        Bl, Sl, _ = x3.shape
        # flatten LOCALLY: merging sharded B and S dims at the shard_map
        # boundary forces XLA into full-replication resharding (measured
        # 53 GB/layer of involuntary all-gather on the pod mesh)
        y = _dense_mlp_sharded(recipe, cfg.act, plan, x3.reshape(Bl * Sl, Dl),
                               w13_l.reshape(Dl, gl * Fl), w2_l, tp=use_tp)
        y = y.reshape(Bl, Sl, Dl)
        if armed:
            # guard stats recorded inside this body are tracers of the
            # shard_map trace — thread them out per-shard, max-merge outside
            sv = quant_stats.drain_stats()
            sv = pvary_missing(sv, all_axes)
            return y, sv[None]
        return y

    fs = plan.fsdp_axis
    dp = plan.dp_axes if B % _axes_prod(plan) == 0 else None
    seq_ax = plan.tp_axis if S % tp_size == 0 else None
    if use_tp:
        tok_spec = P(dp, None, None)
        w13_spec = P(fs, None, plan.tp_axis)
        w2_spec = P(plan.tp_axis, fs)
    else:
        # DP mode: tokens sharded over dp (batch) AND tp (seq) — matches the
        # SP residual sharding exactly: zero boundary resharding
        tok_spec = P(dp, seq_ax, None)
        w13_spec = P(fs, None, None)
        w2_spec = P(None, fs)
    out_specs = (tok_spec, P(all_axes, None)) if armed else tok_spec
    sm = jax.shard_map(body, mesh=plan.mesh,
                       in_specs=(tok_spec, w13_spec, w2_spec),
                       out_specs=out_specs)
    if armed:
        y, sv = sm(x, w13, w2)
        quant_stats.reinject_stats(jnp.max(sv, axis=0))
        return y
    return sm(x, w13, w2)


def _dense_mlp_sharded(recipe, act, plan, xf, w13_l, w2_l, *, tp: bool):
    """Inside shard_map: dense MLP, TP (psum over tp_axis) or DP mode.
    Pads tokens AND the contraction dim to the 128-tile alignment the FP8
    pathway needs (e.g. hymba's d_model=1600); zero rows/cols are exact."""
    T, D = xf.shape
    Tp = (T + 127) // 128 * 128
    Dp = (D + 127) // 128 * 128
    if Tp != T or Dp != D:
        xf = jnp.pad(xf, ((0, Tp - T), (0, Dp - D)))
    if Dp != D:
        w13_l = jnp.pad(w13_l, ((0, Dp - D), (0, 0)))
        w2_l = jnp.pad(w2_l, ((0, 0), (0, Dp - D)))
    x3 = xf.reshape(1, Tp, D if Dp == D else Dp)
    dp = tuple(a for a in plan.dp_axes if a != plan.fsdp_axis)
    if tp:
        wg_axes, gx_axes = dp, (plan.tp_axis,)
    else:
        wg_axes, gx_axes = dp + (plan.tp_axis,), ()
    if recipe.name == "fp8_flow":
        qx = quantize_entry(recipe, x3)
        quant_stats.record_entry_stats("q_entry_mlp", x3, qx)
        y = expert_ffn(recipe, act, wg_axes, gx_axes, qx, w13_l[None],
                       w2_l[None])
    else:
        y = expert_ffn(recipe, act, wg_axes, gx_axes,
                       x3.astype(jnp.bfloat16), w13_l[None], w2_l[None])
    if tp:
        y = jax.lax.psum(y, plan.tp_axis)
    return y[0][:T, :D]


def _moe_stage(cfg, recipe, plan, p, x, decode=False):
    """MoE block.  x: (B, S, D) -> (B, S, D), aux-loss scalar."""
    B, S, D = x.shape
    g = cfg.gate_factor
    mcfg = MoEConfig(n_experts=cfg.n_experts, top_k=cfg.top_k, d_model=D,
                     d_ff=cfg.d_ff_expert, capacity_factor=cfg.capacity_factor,
                     ep_axis=plan.tp_axis, act=cfg.act,
                     dp_axes=(plan.dp_axes if not plan.fsdp_axis else tuple(
                         a for a in plan.dp_axes if a != plan.fsdp_axis)))
    we13, we2, wr = p["we13"], p["we2"], p["w_router"]

    if plan.mesh is None:
        # Fully-local MoE (EP=1, ep_axis=None: every collective an identity).
        # This is the path the DistPlan train step takes: the whole step is
        # already inside a shard_map over the DP axis (repro.dist), so the
        # forward must not open a nested shard_map.
        from repro.core.quant import QTensor as _QT0
        if isinstance(we13, _QT0):
            raise ValueError("W8-resident MoE weights need a mesh plan")
        E_l, Dl, gl, Fl = we13.shape
        mcfg_local = dataclasses.replace(mcfg, ep_axis=None, dp_axes=())
        y, m = moe_block(recipe, mcfg_local, x.reshape(B * S, D), wr,
                         we13.reshape(E_l, Dl, gl * Fl), we2)
        y = y.reshape(B, S, D)
        if cfg.n_shared_experts:
            y = y + _mlp_stage(cfg, recipe, plan,
                               {"w13": p["ws13"], "w2": p["ws2"]}, x)
        return y, jnp.mean(m["aux_loss"])

    gather = plan.fsdp_axis
    # decode-EP only exists when experts are EP-sharded; TP-experts (E < tp)
    # use the same TP block for decode (forward-only)
    mode = (("decode" if plan.moe_mode == "ep" else "tp")
            if decode else plan.moe_mode)

    from repro.core.quant import QTensor as _QT
    w8 = isinstance(we13, _QT)

    def body(xf, wr_l, we13_l, we2_l):
        if w8:
            # W8-resident: fp8 payload + po2 scales live on-chip; no gather,
            # no per-step weight quantization (serve/w8.py)
            from repro.core.fp8 import TILE as _T
            from repro.serve.w8 import retile
            we13_r = retile(we13_l, (1, _T, _T))      # stored (E, D, g*Fe)
            we2_l = retile(we2_l, (1, _T, _T))
        else:
            if gather:
                we13_l = jax.lax.all_gather(we13_l, gather, axis=1,
                                            tiled=True)
                we2_l = jax.lax.all_gather(we2_l, gather, axis=2, tiled=True)
            E_l, Dl, gl, Fl = we13_l.shape
            we13_r = we13_l.reshape(E_l, Dl, gl * Fl)
        if mode == "ep":
            if plan.moe_overlap is not None:
                y, m = moe_block_overlapped(
                    recipe, mcfg, xf, wr_l, we13_r, we2_l,
                    n_chunks=plan.moe_overlap.chunks_for(xf.shape[0]))
            else:
                y, m = moe_block(recipe, mcfg, xf, wr_l, we13_r, we2_l)
        elif mode == "tp":
            y, m = moe_block_tp(recipe, mcfg, xf, wr_l, we13_r, we2_l,
                                tp_axis=plan.tp_axis,
                                combine_mode=plan.moe_tp_combine)
        elif plan.moe_overlap is not None:
            # prefetching decode path: chunk c+1's router/dispatch/expert
            # stages run while chunk c's combine psum is on the wire
            y, m = moe_block_decode_overlapped(
                recipe, mcfg, xf, wr_l, we13_r, we2_l,
                n_chunks=plan.moe_overlap.decode_chunks_for(xf.shape[0]))
        else:
            y, m = moe_block_decode(recipe, mcfg, xf, wr_l, we13_r, we2_l)
        # aux loss leaves the shard_map as a per-shard (1,) array; the mean
        # happens outside (robust to size-1 mesh axes in the vma system)
        aux = m["aux_loss"][None]
        return y, aux

    if mode == "ep":
        tok_axes = plan.token_axes_moe if not decode else plan.dp_axes
        e_spec0 = plan.tp_axis
        out_tok_axes = tok_axes
    else:
        tok_axes = plan.dp_axes
        e_spec0 = None
        out_tok_axes = (tok_axes + (plan.tp_axis,)
                        if plan.moe_tp_combine == "reduce_scatter"
                        else tok_axes)
    if mode == "decode":
        tok_axes = plan.dp_axes
        e_spec0 = plan.tp_axis
        out_tok_axes = tok_axes
    we13_spec = (P(e_spec0, gather, None, None) if mode != "tp"
                 else P(None, gather, None, plan.tp_axis))
    we2_spec = (P(e_spec0, None, gather) if mode != "tp"
                else P(None, plan.tp_axis, gather))
    if w8:
        from repro.core.quant import QTensor as _QT2
        # QTensor weights: spec pytree matches (data, scale); scales shard
        # on the same (leading expert) axis
        we13_spec = _QT2(data=P(e_spec0, None, None),
                         scale=P(e_spec0, None, None),
                         tile=we13.tile)
        we2_spec = _QT2(data=P(e_spec0, None, None),
                        scale=P(e_spec0, None, None), tile=we2.tile)
    # 3D boundary specs (batch over dp, seq over tp where applicable) —
    # merging sharded dims at the boundary forces full-replication resharding
    tp_size = plan.mesh.shape[plan.tp_axis]
    dp3 = plan.dp_axes if B % _axes_prod(plan) == 0 else None
    seq3 = plan.tp_axis if (plan.tp_axis in (tok_axes if isinstance(
        tok_axes, tuple) else (tok_axes,)) and S % tp_size == 0) else None
    out_seq3 = plan.tp_axis if (plan.tp_axis in (out_tok_axes if isinstance(
        out_tok_axes, tuple) else (out_tok_axes,)) and S % tp_size == 0)         else None

    all_axes = tuple(plan.mesh.axis_names)

    armed = quant_stats.stats_armed()

    def body3(x3, wr_l, we13_l, we2_l):
        Bl, Sl, Dl = x3.shape
        y, aux = body(x3.reshape(Bl * Sl, Dl), wr_l, we13_l, we2_l)
        # broadcast the aux scalar onto every mesh axis so one out_spec
        # (sharded over all axes) is valid in every mode/mesh
        aux = pvary_missing(aux, all_axes)
        y = y.reshape(Bl, -1, Dl)
        if armed:
            # guard stats recorded inside this body are tracers of the
            # shard_map trace — thread them out per-shard, max-merge outside
            sv = quant_stats.drain_stats()
            sv = pvary_missing(sv, all_axes)
            return y, aux, sv[None]
        return y, aux

    out3 = (P(dp3, out_seq3, None), P(all_axes)) + \
        ((P(all_axes, None),) if armed else ())
    sm = jax.shard_map(body3, mesh=plan.mesh,
                       in_specs=(P(dp3, seq3, None), P(None, None),
                                 we13_spec, we2_spec),
                       out_specs=out3)

    # Overlap lever (§dispatch pipeline): with moe_overlap set, the shared
    # expert — which depends only on x, never on the dispatch — is ISSUED
    # BEFORE the MoE shard_map, so its dense GEMMs are ready to run while the
    # first chunk's fused dispatch all-to-all is on the wire.  Without the
    # overlap plan it stays after the MoE (the historical ordering).
    shared_out = None
    if (cfg.n_shared_experts and not decode
            and plan.moe_overlap is not None and mode == "ep"):
        shared_out = _mlp_stage(cfg, recipe, plan,
                                {"w13": p["ws13"], "w2": p["ws2"]}, x)

    if armed:
        y, aux, sv = sm(x, wr, we13, we2)
        quant_stats.reinject_stats(jnp.max(sv, axis=0))
    else:
        y, aux = sm(x, wr, we13, we2)
    aux = jnp.mean(aux)

    if cfg.n_shared_experts:
        if shared_out is not None:
            y = y + shared_out
        elif decode:
            y = y + _mlp_decode(cfg, {"w13": p["ws13"], "w2": p["ws2"]}, x)
        else:
            y = y + _mlp_stage(cfg, recipe, plan,
                               {"w13": p["ws13"], "w2": p["ws2"]}, x)
    return y, aux


# ---------------------------------------------------------------------------
# Layer groups + full forward.
# ---------------------------------------------------------------------------
def _residual_constraint(plan, x, decode=False):
    """Sequence-parallel sharding of the residual stream (B, S, D): tokens
    over dp axes AND seq over the model axis.  This is what bounds the
    scan-remat carry memory at scale; XLA inserts the gather/scatter pair
    around attention (Megatron-SP pattern)."""
    if plan.mesh is None or decode:
        return x
    B, S, D = x.shape
    tp = plan.mesh.shape[plan.tp_axis]
    seq_ax = plan.tp_axis if S % tp == 0 else None
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(
            plan.mesh, P(plan.dp_axes if B % _axes_prod(plan) == 0 else None,
                         seq_ax, None)))


def _axes_prod(plan):
    out = 1
    for a in plan.dp_axes:
        out *= plan.mesh.shape[a]
    return out


def _sub_layer(cfg, recipe, plan, kind, moe_layer, p, x, positions,
               cache=None, cache_pos=None, ssm_state=None, conv_state=None,
               causal=True):
    """One transformer layer.  Returns (x, aux, new_cache, new_ssm, new_conv).

    Staged decomposition (models/layers.LAYER_STAGES): stage 'attn' is
    stage_ln_attn (pure-attention kinds) or the mixer fan-out below; the MoE
    stages (router -> dispatch -> expert -> combine) run inside _moe_stage /
    core.moe."""
    aux = jnp.float32(0.0)
    new_cache, new_ssm, new_conv = None, None, None
    decode = cache is not None or ssm_state is not None

    if kind == "ssm":
        h = apply_norm(cfg.norm, x, p, "ln1")
        mix, new_ssm, new_conv = mamba2_block(
            cfg, p, h, state=ssm_state, conv_state=conv_state, decode=decode)
        x = x + mix
    elif kind == "hybrid":
        h = apply_norm(cfg.norm, x, p, "ln1")
        attn_out, new_cache = attn_block(
            cfg, p, h, positions=positions, layer_window=0, cache=cache,
            cache_pos=cache_pos, causal=causal, plan=plan)
        ssm_out, new_ssm, new_conv = mamba2_block(
            cfg, p, h, state=ssm_state, conv_state=conv_state, decode=decode)
        x = x + 0.5 * (attn_out + ssm_out)
    else:
        window = cfg.window if kind == "local" else 0
        x, new_cache = stage_ln_attn(
            cfg, p, x, positions=positions, layer_window=window, cache=cache,
            cache_pos=cache_pos, causal=causal, plan=plan)

    if kind == "ssm" and not cfg.d_ff:      # mamba2: mixer-only blocks
        x = _residual_constraint(plan, x, decode=decode)
        return x, aux, new_cache, new_ssm, new_conv

    from repro.core.quant import tag_saveable
    h2 = tag_saveable(apply_norm(cfg.norm, x, p, "ln2"), "stage_ffn_in")
    if moe_layer:
        if decode:
            mlp_out, aux = _moe_stage(cfg, recipe, plan, p, h2, decode=True)
        else:
            mlp_out, aux = _moe_stage(cfg, recipe, plan, p, h2)
    else:
        # dense layers have no router/dispatch/combine; the whole FFN is
        # one 'expert' stage so profiles line up across layer kinds
        from repro.obs.trace import stage_annotation
        with stage_annotation("expert"):
            mlp_out = _mlp_stage(cfg, recipe, plan, p, h2)
    out = x + mlp_out
    out = _residual_constraint(plan, out, decode=cache is not None
                               or ssm_state is not None)
    return out, aux, new_cache, new_ssm, new_conv


def _run_stack(cfg, recipe, plan, stack_params, pattern, n_layers, moe, x,
               positions, causal=True):
    """Scan over a homogeneous stack of layers, pattern-grouped: the stack is
    reshaped (n_groups, len(pattern), ...) and the pattern is unrolled inside
    the scan body.  Rematerialization is owned by the MemoryPlan
    (train/memory.py): the body is wrapped per cfg.remat_policy, and the
    'pair' policy folds TWO pattern groups into each checkpointed body
    (halving trace sites) when the depth allows."""
    pattern = _pattern_or_fallback(pattern, n_layers)
    mem = MemoryPlan.from_config(cfg)
    glen = len(pattern)
    ng = n_layers // glen
    fold = mem.group_factor(ng)
    if fold > 1:
        pattern = pattern * fold
        glen *= fold
        ng //= fold

    # guard-stats threading (train/guards.py): quantize-site stats recorded
    # inside the scan body are TRACERS of that body — they must ride the
    # carry out (drained in-body, max-merged) and be reinjected at this
    # level.  Unarmed (the default), the carry and jaxpr are unchanged.
    armed = quant_stats.stats_armed()

    def group_body(carry, pslice):
        xc, aux = carry[:2]
        for i in range(glen):
            pi = jax.tree.map(lambda a: a[i], pslice)
            xc, a, _, _, _ = _sub_layer(cfg, recipe, plan, pattern[i], moe,
                                        pi, xc, positions, causal=causal)
            aux = aux + a
        if armed:
            return (xc, aux, jnp.maximum(carry[2],
                                         quant_stats.drain_stats())), None
        return (xc, aux), None

    body = mem.wrap(group_body)
    grouped = jax.tree.map(
        lambda a: a.reshape(ng, glen, *a.shape[1:]), stack_params)
    init = (x, jnp.float32(0.0)) + \
        ((quant_stats.zero_stats(),) if armed else ())
    carry, _ = jax.lax.scan(body, init, grouped)
    x, aux = carry[:2]
    if armed:
        quant_stats.reinject_stats(carry[2])
    return x, aux


# ---------------------------------------------------------------------------
# Staged layer program: the unrolled stack driver + per-layer iteration the
# streaming DP wire (train/train_step._streamed_grads) drives directly.
# ---------------------------------------------------------------------------
def layer_forward(cfg, recipe, plan, kind, moe_layer, p, x, positions,
                  causal=True):
    """One decoder layer of the staged program (train/prefill path):
    stage 'attn', then the MLP/MoE stages (router -> dispatch -> expert ->
    combine inside core.moe).  Returns (x_out, aux) — the differentiable
    unit the per-layer backward emits gradients for."""
    out, aux, _, _, _ = _sub_layer(cfg, recipe, plan, kind, moe_layer, p, x,
                                   positions, causal=causal)
    return out, aux


def _pattern_or_fallback(pattern, n_layers: int):
    """THE single copy of the kind-sequence fallback rule: a pattern whose
    length does not divide the stack depth degrades to its first kind.
    Every stack driver (scan, unrolled, per-layer iteration) derives its
    kinds through here, so the staged backward's layer kinds can never
    desynchronize from the forward's."""
    return pattern if n_layers % len(pattern) == 0 else (pattern[0],)


def stack_patterns(cfg: ArchConfig):
    """(dense_pattern, main_pattern) as every stack driver resolves them."""
    nd = cfg.n_dense_layers if cfg.moe else 0
    return (cfg.pattern[0],), _pattern_or_fallback(cfg.pattern,
                                                   cfg.n_layers - nd)


def iter_layer_slices(cfg: ArchConfig, params):
    """Static per-layer walk of the stacked decoder stacks in forward order:
    yields (stack_name, layer_index, kind, moe_layer, per-layer params).
    The kind sequence matches _run_stack's pattern grouping exactly, so the
    staged and scanned forwards compute the same function."""
    nd = cfg.n_dense_layers if cfg.moe else 0
    dense_pat, main_pat = stack_patterns(cfg)
    if nd and "dense_layers" in params:
        for l in range(nd):
            yield ("dense_layers", l, dense_pat[l % len(dense_pat)], False,
                   jax.tree.map(lambda a, _l=l: a[_l],
                                params["dense_layers"]))
    for j in range(cfg.n_layers - nd):
        yield ("layers", j, main_pat[j % len(main_pat)], cfg.moe,
               jax.tree.map(lambda a, _j=j: a[_j], params["layers"]))


def _run_stack_unrolled(cfg, recipe, plan, stack_params, pattern, n_layers,
                        moe, x, positions, causal=True):
    """Staged (unrolled) stack driver: same math as _run_stack, but each
    layer is its own trace region with a TWO-LAYER CARRY WINDOW — layer L's
    scalar epilogue (the aux-loss landing) is deferred until after layer
    L+1's attn/router/dispatch stages have been issued, and the backward of
    the unrolled program emits per-layer gradient leaves in reverse layer
    order (what the streaming DP wire consumes).  The residual stream
    itself is strictly sequential; the real cross-layer overlap lives in
    the stage pipelines it enables (the chunked dispatch a2a and the
    decode combine-psum chain in core/moe.py).  Rematerialization is owned
    by the MemoryPlan: each checkpoint block holds one layer (or two under
    the 'pair' policy — the compile-time lever)."""
    pattern = _pattern_or_fallback(pattern, n_layers)
    mem = MemoryPlan.from_config(cfg)
    armed = quant_stats.stats_armed()
    aux = jnp.float32(0.0)
    pending = None                  # the two-layer window's deferred scalar
    for blk in mem.layer_blocks(n_layers):
        ps = tuple(jax.tree.map(lambda a, _l=l: a[_l], stack_params)
                   for l in blk)
        kinds = tuple(pattern[l % len(pattern)] for l in blk)

        def f(ps_, xc, _kinds=kinds):
            a_blk = jnp.float32(0.0)
            for p, kind in zip(ps_, _kinds):
                xc, a = layer_forward(cfg, recipe, plan, kind, moe, p, xc,
                                      positions, causal=causal)
                a_blk = a_blk + a
            if armed:   # guard stats: drained in-block, threaded out
                return xc, a_blk, quant_stats.drain_stats()
            return xc, a_blk

        if armed:
            x, a, sv = mem.wrap(f)(ps, x)
            quant_stats.reinject_stats(sv)
        else:
            x, a = mem.wrap(f)(ps, x)
        if pending is not None:     # the previous block's epilogue lands
            aux = aux + pending     # only after this block was issued
        pending = a
    if pending is not None:
        aux = aux + pending
    return x, aux


# ---------------------------------------------------------------------------
# Forward (train / prefill) + loss.
# ---------------------------------------------------------------------------
def _embed_tokens(cfg, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _lm_logits(cfg, params, x, plan=None):
    """Logits stay BF16 and VOCAB-SHARDED over the model axis; the residual
    enters seq-gathered so the two 'model' shardings never conflict (else XLA
    replicates the (T, V) tensor — 2.3 GiB/device at 152k vocab)."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if plan is not None and plan.mesh is not None:
        B, S, D = x.shape
        dp = plan.dp_axes if B % _axes_prod(plan) == 0 else None
        x = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(plan.mesh, P(dp, None, None)))
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    if plan is not None and plan.mesh is not None:
        Vp = logits.shape[-1]
        v_ax = plan.tp_axis if Vp % plan.mesh.shape[plan.tp_axis] == 0 else None
        logits = jax.lax.with_sharding_constraint(
            logits, jax.sharding.NamedSharding(plan.mesh, P(dp, None, v_ax)))
    if cfg.final_softcap:
        logits = (cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap)).astype(logits.dtype)
    if cfg.vocab_padded != cfg.vocab:
        pad_mask = jnp.arange(cfg.vocab_padded) >= cfg.vocab
        logits = jnp.where(pad_mask[None, None, :],
                           jnp.asarray(-1e4, logits.dtype), logits)
    return logits   # BF16, vocab-sharded — f32 only inside the CE kernel


@jax.custom_vjp
def _xent(logits, targets, mask):
    """Cross-entropy over BF16 vocab-sharded logits.  The custom VJP keeps
    both the forward reductions and the backward dlogits in BF16 payloads
    (f32 math fused elementwise) — the (T, V) tensor never exists in f32."""
    loss, _ = _xent_fwd_impl(logits, targets, mask)
    return loss


def _xent_fwd_impl(logits, targets, mask):
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1)) + m
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum((lse - gold) * mask) / denom
    return loss, (logits, targets, mask, lse, denom)


def _xent_fwd(logits, targets, mask):
    return _xent_fwd_impl(logits, targets, mask)


def _xent_bwd(res, g):
    logits, targets, mask, lse, denom = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    dlogits = (p - onehot) * (mask * g / denom)[..., None]
    return dlogits.astype(logits.dtype), None, None


_xent.defvjp(_xent_fwd, _xent_bwd)


def forward(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan, params,
            batch, compute_loss=True):
    """batch: {'tokens' (B,S_tok) int32, 'targets' (B,S_tok), 'mask' (B,S_tok),
    optional 'prefix' (B,P,D) [vlm/audio frontend stub embeddings],
    optional 'enc_input' (B,S_enc,D) [seamless]}.
    Returns (loss, metrics) or (logits, metrics)."""
    tokens = batch["tokens"]
    x = _embed_tokens(cfg, params, tokens)
    if cfg.frontend != "none" and "prefix" in batch:
        x = jnp.concatenate([batch["prefix"].astype(x.dtype), x], axis=1)
    B, S, D = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)
    aux_total = jnp.float32(0.0)

    cross_kv_src = None
    if cfg.encdec:
        enc = batch["enc_input"].astype(x.dtype)
        enc_pos = jnp.arange(enc.shape[1], dtype=jnp.int32)
        enc, aux_e = _run_stack(cfg, recipe, plan, params["enc_layers"],
                                ("global",), cfg.n_enc_layers, False, enc,
                                enc_pos, causal=False)
        aux_total += aux_e
        enc = apply_norm(cfg.norm, enc, {"enc_norm_s": None} if False else
                         {"final_norm_s": params["final_norm_s"],
                          "final_norm_b": params.get("final_norm_b")},
                         "final_norm")
        cross_kv_src = enc

    # staged (unrolled, two-layer window) vs monolithic-scan stack driver
    run_stack = _run_stack_unrolled if plan.stage_layers else _run_stack

    nd = cfg.n_dense_layers if cfg.moe else 0
    if nd:
        x, aux_d = run_stack(cfg, recipe, plan, params["dense_layers"],
                             (cfg.pattern[0],), nd, False, x, positions)
        aux_total += aux_d

    if cfg.encdec:
        x, aux_m = _run_encdec_decoder(cfg, recipe, plan, params, x,
                                       positions, cross_kv_src)
    else:
        x, aux_m = run_stack(cfg, recipe, plan, params["layers"], cfg.pattern,
                             cfg.n_layers - nd, cfg.moe, x, positions)
    aux_total += aux_m

    x = apply_norm(cfg.norm, x, {"final_norm_s": params["final_norm_s"],
                                 "final_norm_b": params.get("final_norm_b")},
                   "final_norm")
    if cfg.frontend != "none" and "prefix" in batch:
        x = x[:, batch["prefix"].shape[1]:]
    logits = _lm_logits(cfg, params, x, plan)
    metrics = {"aux_loss": aux_total}
    if quant_stats.stats_armed():
        # final drain: every stack driver reinjected its threaded stats at
        # this level, so the merged matrix exits value_and_grad via has_aux
        sv = quant_stats.drain_stats()
        sm = quant_stats.site_maxima(sv)
        metrics["quant_sat_frac"] = sm[0]
        metrics["quant_flush_frac"] = sm[1]
        metrics["quant_site_stats"] = sv
    if not compute_loss:
        return logits, metrics
    mask = batch.get("mask", jnp.ones_like(tokens, jnp.float32))
    loss = _xent(logits, batch["targets"], mask) + AUX_LOSS_COEF * aux_total
    metrics["loss"] = loss
    return loss, metrics


def _run_encdec_decoder(cfg, recipe, plan, params, x, positions, enc):
    """Decoder stack with cross-attention (scanned; cross params stacked)."""
    armed = quant_stats.stats_armed()

    def group_body(carry, pslice):
        xc, aux = carry[:2]
        p_self, p_cross = pslice
        xc, a, _, _, _ = _sub_layer(cfg, recipe, plan, "global", cfg.moe,
                                    p_self, xc, positions)
        h = rms_or_ln(cfg, xc, p_cross)
        from repro.models.layers import attn_block as _ab
        kv = _project_cross_kv(cfg, p_cross, enc)
        c_out, _ = _ab(cfg, p_cross, h, positions=positions, cross_kv=kv)
        xc = xc + c_out
        aux = aux + a
        if armed:
            return (xc, aux, jnp.maximum(carry[2],
                                         quant_stats.drain_stats())), None
        return (xc, aux), None

    body = MemoryPlan.from_config(cfg).wrap(group_body)
    init = (x, jnp.float32(0.0)) + \
        ((quant_stats.zero_stats(),) if armed else ())
    carry, _ = jax.lax.scan(
        body, init, (params["layers"], params["cross_layers"]))
    if armed:
        quant_stats.reinject_stats(carry[2])
    return carry[0], carry[1]


def rms_or_ln(cfg, x, p_cross):
    from repro.models.layers import rmsnorm
    return rmsnorm(x, p_cross["ln_s"])


def _project_cross_kv(cfg, p, enc):
    B, Se, D = enc.shape
    KV, hd = cfg.n_kv, cfg.head_dim
    k = jnp.einsum("bsd,dn->bsn", enc, p["wk"].astype(enc.dtype))
    v = jnp.einsum("bsd,dn->bsn", enc, p["wv"].astype(enc.dtype))
    return k.reshape(B, Se, KV, hd), v.reshape(B, Se, KV, hd)


# ---------------------------------------------------------------------------
# Serving: KV/SSM caches + single-token decode step.
# ---------------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               cache_dtype=jnp.bfloat16, fp8_kv: bool = False):
    """Cache pytree.  fp8_kv stores K/V payloads in e4m3 with per-(token,
    head) po2 scales — the beyond-paper KV-compression option (halves the
    decode memory-roofline term)."""
    kinds = layer_kinds(cfg)
    nd = cfg.n_dense_layers if cfg.moe else 0
    KV, hd = cfg.n_kv, cfg.head_dim
    kv_dtype = jnp.float8_e4m3fn if fp8_kv else cache_dtype

    def attn_cache(n):
        return {
            "k": jnp.zeros((n, batch, max_len, KV, hd), kv_dtype),
            "v": jnp.zeros((n, batch, max_len, KV, hd), kv_dtype),
        }

    def ssm_cache(n):
        di, N = cfg.d_inner, cfg.ssm_state
        H, Pd = cfg.ssm_heads, cfg.ssm_headdim
        return {
            "state": jnp.zeros((n, batch, H, Pd, N), jnp.float32),
            "conv": jnp.zeros((n, batch, cfg.ssm_conv - 1, di + 2 * N),
                              jnp.float32),
        }

    cache = {}
    main_kinds = kinds[nd:]
    n_main = len(main_kinds)
    if any(k != "ssm" for k in main_kinds):
        cache["main_attn"] = attn_cache(n_main)
    if any(k in ("ssm", "hybrid") for k in main_kinds):
        cache["main_ssm"] = ssm_cache(n_main)
    if nd:
        cache["dense_attn"] = attn_cache(nd)
    if cfg.encdec:
        # cross-attention K/V are computed once at prefill and fixed
        cache["cross"] = {
            "k": jnp.zeros((cfg.n_layers, batch, max_len, KV, hd), cache_dtype),
            "v": jnp.zeros((cfg.n_layers, batch, max_len, KV, hd), cache_dtype),
        }
    return cache


def _cache_rw(cfg, p, kind, x, positions, pos, kc, vc, recipe, plan,
              moe_layer):
    """One decode layer given its cache slices; returns (x, new_k, new_v...)."""
    raise NotImplementedError  # folded into decode_step's scan body below


def decode_step(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan, params,
                cache, tokens, pos):
    """One decode step.  tokens: (B, 1) int32; pos: scalar int32 (shared
    position — the fixed-batch bench path) OR (B,) int32 per-request
    positions (continuous batching; cache rows [0, pos_b) are filled).
    Returns (logits (B,1,V), new_cache)."""
    x = _embed_tokens(cfg, params, tokens)
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((1,), pos,
                                                            jnp.int32)
    kinds = layer_kinds(cfg)
    nd = cfg.n_dense_layers if cfg.moe else 0
    new_cache = dict(cache)

    def run_decode_stack(x, stack_params, stack_kinds, moe, attn_c, ssm_c,
                         cross_c=None, cross_params=None):
        glen = len(stack_kinds) if len(set(stack_kinds)) > 1 else 1
        n = len(stack_kinds)
        # decode scans layer-by-layer (glen folded in as static python loop
        # is unnecessary: window flag differs per layer kind, so scan groups)
        pat = cfg.pattern if n % len(cfg.pattern) == 0 else (stack_kinds[0],)
        glen = len(pat)
        ng = n // glen

        def body(carry, xs):
            xc = carry
            pslice = xs["p"]
            outs = {}
            for i in range(glen):
                pi = jax.tree.map(lambda a: a[i], pslice)
                kind = pat[i]
                kc = vc = st = cv = None
                if attn_c is not None:
                    kc = xs["k"][i]
                    vc = xs["v"][i]
                if ssm_c is not None:
                    st = xs["state"][i]
                    cv = xs["conv"][i]
                window = cfg.window if kind == "local" else 0
                aux = jnp.float32(0.0)
                h = apply_norm(cfg.norm, xc, pi, "ln1")
                nk = nv = nst = ncv = None
                if kind == "ssm":
                    mix, nst, ncv = mamba2_block(cfg, pi, h, state=st,
                                                 conv_state=cv, decode=True)
                elif kind == "hybrid":
                    a_out, (nk, nv) = attn_block(
                        cfg, pi, h, positions=positions, layer_window=0,
                        cache=(kc, vc), cache_pos=pos)
                    s_out, nst, ncv = mamba2_block(cfg, pi, h, state=st,
                                                   conv_state=cv, decode=True)
                    mix = 0.5 * (a_out + s_out)
                else:
                    mix, (nk, nv) = attn_block(
                        cfg, pi, h, positions=positions, layer_window=window,
                        cache=(kc, vc), cache_pos=pos)
                xc = xc + mix
                if cross_params is not None:
                    pc = xs["pc"]
                    hc = rms_or_ln(cfg, xc, pc)
                    ck = xs["ck"]
                    cv_ = xs["cv_"]
                    c_out, _ = attn_block(cfg, pc, hc, positions=positions,
                                          cache=(ck, cv_), cache_pos=pos,
                                          cross_kv=(ck.astype(hc.dtype),
                                                    cv_.astype(hc.dtype)))
                    xc = xc + c_out
                if not (kind == "ssm" and not cfg.d_ff):
                    h2 = apply_norm(cfg.norm, xc, pi, "ln2")
                    if moe:
                        mo, _ = _moe_stage(cfg, recipe, plan, pi, h2,
                                           decode=True)
                    else:
                        mo = _mlp_decode(cfg, pi, h2)
                    xc = xc + mo
                outs.setdefault("k", []).append(nk)
                outs.setdefault("v", []).append(nv)
                outs.setdefault("state", []).append(nst)
                outs.setdefault("conv", []).append(ncv)
            emit = {}
            if attn_c is not None:
                emit["k"] = jnp.stack([o if o is not None else xs["k"][i]
                                       for i, o in enumerate(outs["k"])])
                emit["v"] = jnp.stack([o if o is not None else xs["v"][i]
                                       for i, o in enumerate(outs["v"])])
            if ssm_c is not None:
                emit["state"] = jnp.stack(
                    [o if o is not None else xs["state"][i]
                     for i, o in enumerate(outs["state"])])
                emit["conv"] = jnp.stack(
                    [o if o is not None else xs["conv"][i]
                     for i, o in enumerate(outs["conv"])])
            return xc, emit

        xs = {"p": jax.tree.map(
            lambda a: a.reshape(ng, glen, *a.shape[1:]), stack_params)}
        if attn_c is not None:
            xs["k"] = attn_c["k"].reshape(ng, glen, *attn_c["k"].shape[1:])
            xs["v"] = attn_c["v"].reshape(ng, glen, *attn_c["v"].shape[1:])
        if ssm_c is not None:
            xs["state"] = ssm_c["state"].reshape(
                ng, glen, *ssm_c["state"].shape[1:])
            xs["conv"] = ssm_c["conv"].reshape(
                ng, glen, *ssm_c["conv"].shape[1:])
        if cross_params is not None:
            xs["pc"] = cross_params
            xs["ck"] = cross_c["k"]
            xs["cv_"] = cross_c["v"]
        x, emits = jax.lax.scan(body, x, xs)
        out_attn = out_ssm = None
        if attn_c is not None:
            out_attn = {"k": emits["k"].reshape(n, *emits["k"].shape[2:]),
                        "v": emits["v"].reshape(n, *emits["v"].shape[2:])}
        if ssm_c is not None:
            out_ssm = {
                "state": emits["state"].reshape(n, *emits["state"].shape[2:]),
                "conv": emits["conv"].reshape(n, *emits["conv"].shape[2:])}
        return x, out_attn, out_ssm

    if nd:
        x, d_attn, _ = run_decode_stack(
            x, params["dense_layers"], kinds[:nd], False,
            cache.get("dense_attn"), None)
        new_cache["dense_attn"] = d_attn

    x, m_attn, m_ssm = run_decode_stack(
        x, params["layers"], kinds[nd:], cfg.moe,
        cache.get("main_attn"), cache.get("main_ssm"),
        cross_c=cache.get("cross"),
        cross_params=params.get("cross_layers"))
    if m_attn is not None:
        new_cache["main_attn"] = m_attn
    if m_ssm is not None:
        new_cache["main_ssm"] = m_ssm

    x = apply_norm(cfg.norm, x, {"final_norm_s": params["final_norm_s"],
                                 "final_norm_b": params.get("final_norm_b")},
                   "final_norm")
    logits = _lm_logits(cfg, params, x, plan)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged serving: chunked prefill + per-request decode over paged KV pools
# (serve/paged_kv.py).  Each request sits at its own depth (`pos` is a (B,)
# vector), KV rows live in fixed-size pages addressed through per-request
# page tables, and page payloads are FP8-e4m3 with per-row po2 scales (or
# BF16 with the fallback pools).  Attention-only decoder stacks.
# ---------------------------------------------------------------------------
def _run_paged_stack(cfg, recipe, plan, stack_params, stack_kinds, moe, x,
                     pool, positions, page_idx, slot_idx, *, decode,
                     page_tables=None, pos=None, history=False):
    """Scan a layer stack against its paged K/V pools.

    pool: {"k": {"data" (n,P,ps,KV,hd) [, "scale"]}, "v": {...}}.
    page_idx/slot_idx: (N,) write coordinates for this step's rows (scratch
    page 0 for masked rows).  decode=True reads the paged history through
    `page_tables` and masks by per-request `pos`; decode=False (prefill) runs
    causal flash attention over the in-flight chunk — with history=True
    (a chunked-prefill CONTINUATION) the chunk's queries additionally attend
    to the previously prefilled rows, read back through `page_tables` after
    this chunk's rows are written (absolute-position causal masking keeps
    unwritten/scratch rows out of every receptive field).
    Returns (x, new_pool)."""
    from repro.models.layers import flash_attention, project_qkv
    from repro.obs.trace import stage_annotation
    from repro.serve.paged_kv import page_read, page_write_rows

    n = len(stack_kinds)
    pat = cfg.pattern if n % len(cfg.pattern) == 0 else (stack_kinds[0],)
    glen = len(pat)
    ng = n // glen

    def attend(xc, pi, kc, vc, window):
        """ln1 -> q/k/v -> this step's rows into the pages -> attention
        (over the paged history for decode and continuations) -> wo."""
        h = apply_norm(cfg.norm, xc, pi, "ln1")
        q, k, v = project_qkv(cfg, pi, h, positions)
        rows_k = k[:, 0] if decode else k[0]
        rows_v = v[:, 0] if decode else v[0]
        kc = page_write_rows(kc, rows_k, page_idx, slot_idx)
        vc = page_write_rows(vc, rows_v, page_idx, slot_idx)
        if decode:
            from repro.models.layers import decode_attention
            kd = page_read(kc, page_tables, jnp.bfloat16)
            vd = page_read(vc, page_tables, jnp.bfloat16)
            o = decode_attention(q, kd.astype(q.dtype),
                                 vd.astype(q.dtype), pos=pos,
                                 window=window, softcap=cfg.attn_softcap)
        elif history:
            # chunked-prefill continuation: attend over the request's
            # full paged history (this chunk's rows included — they were
            # just written above) with absolute-position causal masking
            kd = page_read(kc, page_tables, jnp.bfloat16)
            vd = page_read(vc, page_tables, jnp.bfloat16)
            Skv = kd.shape[1]
            bk = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1)
                      if Skv % b == 0)
            o = flash_attention(q, kd.astype(q.dtype), vd.astype(q.dtype),
                                q_pos=positions,
                                kv_pos=jnp.arange(Skv, dtype=jnp.int32),
                                causal=True, window=window,
                                softcap=cfg.attn_softcap, block_k=bk)
        else:
            o = flash_attention(q, k, v, q_pos=positions,
                                kv_pos=positions, causal=True,
                                window=window, softcap=cfg.attn_softcap)
        B, S = xc.shape[:2]
        mix = jnp.einsum("bsn,nd->bsd", o.reshape(B, S, -1),
                         pi["wo"].astype(xc.dtype))
        return xc + mix, kc, vc

    def body(xc, xs):
        new_pools = []
        for i in range(glen):
            pi = jax.tree.map(lambda a: a[i], xs["p"])
            kc = jax.tree.map(lambda a: a[i], xs["k"])
            vc = jax.tree.map(lambda a: a[i], xs["v"])
            window = cfg.window if pat[i] == "local" else 0
            with stage_annotation("attn"):
                xc, kc, vc = attend(xc, pi, kc, vc, window)
            h2 = apply_norm(cfg.norm, xc, pi, "ln2")
            if moe:
                mo, _ = _moe_stage(cfg, recipe, plan, pi, h2, decode=decode)
            else:
                with stage_annotation("ffn"):
                    mo = _mlp_decode(cfg, pi, h2) if decode \
                        else _mlp_stage(cfg, recipe, plan, pi, h2)
            xc = xc + mo
            new_pools.append({"k": kc, "v": vc})
        emit = jax.tree.map(lambda *ys: jnp.stack(ys), *new_pools)
        return xc, emit

    grouped = lambda t: jax.tree.map(
        lambda a: a.reshape(ng, glen, *a.shape[1:]), t)
    xs = {"p": grouped(stack_params), "k": grouped(pool["k"]),
          "v": grouped(pool["v"])}
    x, emits = jax.lax.scan(body, x, xs)
    new_pool = jax.tree.map(lambda a: a.reshape(n, *a.shape[2:]), emits)
    return x, new_pool


def _paged_stacks(cfg):
    """(kinds, nd) after validating the arch is paged-serving capable."""
    kinds = layer_kinds(cfg)
    if cfg.encdec or cfg.frontend != "none" or any(
            k in ("ssm", "hybrid") for k in kinds):
        raise NotImplementedError(
            "paged serving supports attention-only decoder stacks")
    return kinds, (cfg.n_dense_layers if cfg.moe else 0)


def paged_decode_step(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan,
                      params, pools, page_tables, tokens, pos, active):
    """One continuous-batching decode step over paged pools.

    tokens (B, 1) int32; pos (B,) int32 per-request positions (this token's
    position; rows [0, pos_b) are resident); active (B,) bool — inactive
    slots write to the scratch page and their outputs are garbage;
    page_tables (B, max_pages) int32.  Returns (logits (B,1,V), new_pools)."""
    kinds, nd = _paged_stacks(cfg)
    x = _embed_tokens(cfg, params, tokens)
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]
    ps = pools["main_attn"]["k"]["data"].shape[2]
    page_idx = jnp.where(active,
                         page_tables[jnp.arange(B), pos // ps], 0)
    slot_idx = pos % ps

    new_pools = dict(pools)
    if nd:
        x, new_pools["dense_attn"] = _run_paged_stack(
            cfg, recipe, plan, params["dense_layers"], kinds[:nd], False, x,
            pools["dense_attn"], positions, page_idx, slot_idx, decode=True,
            page_tables=page_tables, pos=pos)
    x, new_pools["main_attn"] = _run_paged_stack(
        cfg, recipe, plan, params["layers"], kinds[nd:], cfg.moe, x,
        pools["main_attn"], positions, page_idx, slot_idx, decode=True,
        page_tables=page_tables, pos=pos)

    x = apply_norm(cfg.norm, x, {"final_norm_s": params["final_norm_s"],
                                 "final_norm_b": params.get("final_norm_b")},
                   "final_norm")
    logits = _lm_logits(cfg, params, x, plan)
    return logits, new_pools


def paged_prefill(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan,
                  params, pools, page_table_row, tokens, length,
                  start=None, history: bool = False):
    """Prefill ONE request's prompt chunk into its pages.

    tokens (1, S) int32, right-padded to the static bucket S (a power of two
    so flash blocking divides); length: scalar int32 valid token count IN
    THIS CHUNK; page_table_row (max_pages,) int32.  Rows >= length land on
    the scratch page; causal masking keeps them out of every valid query's
    receptive field.

    Chunked prefill: `start` (scalar int32, default 0) offsets this chunk
    inside the prompt — positions/RoPE/page coordinates are absolute — and
    history=True (static) makes the chunk's queries attend to the previously
    prefilled rows [0, start) through the page table.  Returns
    (logits (1, 1, V) at absolute position start+length-1, new_pools)."""
    kinds, nd = _paged_stacks(cfg)
    x = _embed_tokens(cfg, params, tokens)
    S = x.shape[1]
    rel = jnp.arange(S, dtype=jnp.int32)
    start = jnp.int32(0) if start is None else jnp.asarray(start, jnp.int32)
    positions = start + rel
    ps = pools["main_attn"]["k"]["data"].shape[2]
    page_idx = jnp.where(rel < length, page_table_row[positions // ps], 0)
    slot_idx = positions % ps

    new_pools = dict(pools)
    if nd:
        x, new_pools["dense_attn"] = _run_paged_stack(
            cfg, recipe, plan, params["dense_layers"], kinds[:nd], False, x,
            pools["dense_attn"], positions, page_idx, slot_idx, decode=False,
            page_tables=page_table_row[None], history=history)
    x, new_pools["main_attn"] = _run_paged_stack(
        cfg, recipe, plan, params["layers"], kinds[nd:], cfg.moe, x,
        pools["main_attn"], positions, page_idx, slot_idx, decode=False,
        page_tables=page_table_row[None], history=history)

    x = apply_norm(cfg.norm, x, {"final_norm_s": params["final_norm_s"],
                                 "final_norm_b": params.get("final_norm_b")},
                   "final_norm")
    x_last = jax.lax.dynamic_slice_in_dim(
        x, jnp.clip(length - 1, 0, S - 1), 1, axis=1)
    logits = _lm_logits(cfg, params, x_last, plan)
    return logits, new_pools


def _mlp_decode(cfg, p, x):
    """Forward-only dense MLP for decode (BF16 einsum; TP via pjit auto)."""
    B, S, D = x.shape
    w13 = p["w13"].astype(x.dtype)                    # (D, g, F)
    h = jnp.einsum("bsd,dgf->bsgf", x, w13)
    if cfg.gate_factor == 2:
        gt, up = h[..., 0, :], h[..., 1, :]
        gf = gt.astype(jnp.float32)
        a = (jax.nn.silu(gf) if cfg.act == "swiglu"
             else jax.nn.gelu(gf, approximate=True)) * up.astype(jnp.float32)
    else:
        hf = h[..., 0, :].astype(jnp.float32)
        a = jax.nn.gelu(hf, approximate=True) if cfg.act == "gelu" \
            else jax.nn.relu(hf)
    return jnp.einsum("bsf,fd->bsd", a.astype(x.dtype),
                      p["w2"].astype(x.dtype))
