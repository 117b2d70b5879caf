"""The MoE block: router -> FP8 dispatch (all-to-all) -> fused permute+pad ->
grouped expert FFN -> combine.  DeepEP-style dataflow mapped onto
shard_map + jax.lax collectives (DESIGN.md §2, §4).

Token layout inside the shard_map body (one device's shard):
  x_loc : (T, D) bf16 tokens (T = local token count)
  EP    : size of the expert-parallel mesh axis ('model')
  E_loc : experts resident on this device (E_total / EP)

Dispatch uses fixed per-destination capacity C_send (static shapes for XLA),
dropping overflow assignments (standard capacity-factor routing; the drop
fraction is returned as a metric).  The send buffer is built by the fused
permute+pad operator directly in FP8 (fp8 recipes) so the all-to-all carries
1-byte payloads + po2 scales (the paper's 'doubled buffers' caveat — both are
counted by the collective roofline term).

Gradient flow (fp8_flow): the dispatch path is FP8 in BOTH directions — the
input-gradient cotangent is a QTensor whose payload rides the backward
all-to-all in e4m3 (paper Fig. 2d), produced by the Dgrad1 fused-quantizing
epilogue in linear.py.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import casts
from repro.core.fp8 import TILE
from repro.core.linear import (_q_row, _quant_weights, dequantize_exit,
                               expert_ffn, ffn_bwd_fp8_core, ffn_fwd_fp8_core,
                               quantize_entry)
from repro.core.quant import (QTensor, _dequantize_nocount, quantize_rowwise,
                              record_entry_stats,
                              tag_saveable)
from repro.core.recipes import Recipe
from repro.obs.trace import stage_annotation


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                      # per-expert hidden (F); w13 is (K, 2F)
    capacity_factor: float = 1.25
    ep_axis: str = "model"         # mesh axis carrying experts
    dp_axes: tuple = ("data",)     # token-sharded axes over which expert
                                   # weights are replicated (Wgrad psum set)
    act: str = "swiglu"
    router_dtype: str = "float32"
    # experts-per-device < 1 is impossible; if n_experts < EP the layer falls
    # back to TP-sharded experts (grok-1 case) — handled in models/lm.py by
    # calling moe_block_tp instead.


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# ---------------------------------------------------------------------------
# Routing (BF16/FP32 — routers are numerically sensitive; all recipes agree).
# ---------------------------------------------------------------------------
def router_topk(x, w_router, top_k: int):
    """Returns (probs (T,k) f32, ids (T,k) i32, aux_loss scalar)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)            # (T, E)
    p, ids = jax.lax.top_k(probs_full, top_k)               # (T, k)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-9)
    # Switch-style load-balance loss + router z-loss
    E = w_router.shape[-1]
    me = jnp.mean(probs_full, axis=0)
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return p, ids, lb_loss + 1e-3 * z_loss


# ---------------------------------------------------------------------------
# Static routing plan: slot maps for send / expert-grouping / combine.
# All pure integer ops (argsort + cumsum); differentiation never touches them.
# ---------------------------------------------------------------------------
def _dispatch_plan(ids, top_k: int, EP: int, E_loc: int, C_send: int):
    """ids: (T, k) global expert ids.  Returns
    row_map_send : (EP*C_send,) source token row per send slot (-1 pad)
    slot_expert  : (EP*C_send,) LOCAL expert id on the dest rank (-1 pad)
    slot_assign  : (EP*C_send,) flat assignment index (for prob lookup; -1)
    drop_frac    : scalar f32
    """
    T = ids.shape[0]
    A = T * top_k
    flat_ids = ids.reshape(A)                      # global expert per assignment
    dest = flat_ids // E_loc                       # dest EP rank
    # stable sort by dest keeps token order within each destination
    order = jnp.argsort(dest, stable=True)
    sorted_dest = dest[order]
    # position within destination group
    pos_all = jnp.arange(A) - jnp.searchsorted(sorted_dest, sorted_dest)
    keep = pos_all < C_send
    slot = sorted_dest * C_send + pos_all          # target send slot
    slot = jnp.where(keep, slot, EP * C_send)      # overflow -> scratch slot
    n_slots = EP * C_send
    init = jnp.full((n_slots + 1,), -1, jnp.int32)
    row_map_send = init.at[slot].set((order // top_k).astype(jnp.int32))[:-1]
    slot_expert = init.at[slot].set((flat_ids[order] % E_loc).astype(jnp.int32))[:-1]
    slot_assign = init.at[slot].set(order.astype(jnp.int32))[:-1]
    drop_frac = 1.0 - jnp.sum(keep.astype(jnp.float32)) / A
    return row_map_send, slot_expert, slot_assign, drop_frac


def _expert_plan(recv_expert, E_loc: int, C_exp: int):
    """recv_expert: (R,) local expert id per received row (-1 invalid).
    Returns row_map_exp (E_loc*C_exp,) source recv-row per expert slot (-1
    pad) and ret_map (R,) expert slot per recv row (-1 dropped)."""
    R = recv_expert.shape[0]
    e = jnp.where(recv_expert >= 0, recv_expert, E_loc)  # invalid -> bucket E
    order = jnp.argsort(e, stable=True)
    sorted_e = e[order]
    pos = jnp.arange(R) - jnp.searchsorted(sorted_e, sorted_e)
    keep = (pos < C_exp) & (sorted_e < E_loc)
    slot = jnp.where(keep, sorted_e * C_exp + pos, E_loc * C_exp)
    init = jnp.full((E_loc * C_exp + 1,), -1, jnp.int32)
    row_map_exp = init.at[slot].set(order.astype(jnp.int32))[:-1]
    ret_init = jnp.full((R + 1,), -1, jnp.int32)
    ret_map = ret_init.at[jnp.where(keep, order, R)].set(
        jnp.where(keep, slot, -1).astype(jnp.int32))[:-1]
    return row_map_exp, ret_map


def _expert_loads(row_map_exp, E_loc: int, C_exp: int):
    """Per-expert live-row counts from the expert plan — the ``masked_m``
    vector of the masked grouped-GEMM layout.  _expert_plan fills each
    expert's slots contiguously from 0, so the count IS the live prefix
    length (rows >= count are the zero-padded dead slots)."""
    return jnp.sum((row_map_exp.reshape(E_loc, C_exp) >= 0),
                   axis=1, dtype=jnp.int32)


def _masked_m_or_none(recipe: Recipe, row_map_exp, E_loc: int, C_exp: int):
    """masked_m for the grouped FFN when the recipe opts in (fp8_flow only —
    the masked kernels live on the FP8 pathway; other recipes ignore it)."""
    if recipe.masked_experts and recipe.name == "fp8_flow":
        return _expert_loads(row_map_exp, E_loc, C_exp)
    return None


# ---------------------------------------------------------------------------
# QTensor-aware permute with explicit VJP (casting-free routing of FP8
# cotangents through injective maps).
# ---------------------------------------------------------------------------
def _take_rows(x, row_map, fill=0.0):
    valid = (row_map >= 0)[:, None]
    rows = jnp.take(x, jnp.maximum(row_map, 0), axis=0)
    return jnp.where(valid, rows, jnp.asarray(fill, x.dtype))


def _permute_pad_fields(data, scale, row_map, use_pallas: bool):
    if use_pallas:
        from repro.kernels import interpret_mode
        from repro.kernels.fused_permute_pad import fused_permute_pad_pallas
        return fused_permute_pad_pallas(data, scale, row_map, row_map.shape[0],
                                        interpret=interpret_mode())
    return _take_rows(data, row_map), _take_rows(scale, row_map, fill=1.0)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def permute_q(recipe: Recipe, q: QTensor, row_map, inv_map) -> QTensor:
    """Gather QTensor rows by row_map (fused permute+pad).  row_map must be
    injective on valid slots; backward gathers by inv_map — FP8 cotangents
    route without any dequantization."""
    d, s = _permute_pad_fields(q.data, q.scale, row_map, recipe.use_pallas)
    return QTensor(d, s, q.tile)


def _pq_fwd(recipe, q, row_map, inv_map):
    return permute_q(recipe, q, row_map, inv_map), (inv_map,)


def _pq_bwd(recipe, res, qg: QTensor):
    (inv_map,) = res
    d, s = _permute_pad_fields(qg.data, qg.scale, inv_map, recipe.use_pallas)
    return QTensor(d, s, qg.tile), None, None


permute_q.defvjp(_pq_fwd, _pq_bwd)


# ---------------------------------------------------------------------------
# Dispatch boundaries (entry quantize fused with the send permute).
# ---------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(0, 3))
def dispatch_quantize(recipe: Recipe, x, row_map, T: int) -> QTensor:
    """fp8_flow entry: ONE explicit quantize (the paper's entry-point cast),
    then the fused permute+pad into the padded send layout.
    Backward: FP8 gradient rows arrive from the backward all-to-all; they are
    dequantized inside the consuming scatter-add (fused) and summed per
    source token (the top-k reduction, kept in BF16 by design)."""
    q = quantize_rowwise(x, scale_mode=recipe.scale_mode, tag="q_entry")
    d, s = _permute_pad_fields(q.data, q.scale, row_map, recipe.use_pallas)
    return QTensor(d, s, q.tile)


def _dq_fwd(recipe, x, row_map, T):
    return dispatch_quantize(recipe, x, row_map, T), (row_map,
                                                      jnp.zeros((0,), x.dtype))


def _dq_bwd(recipe, T, res, qg: QTensor):
    row_map, wit = res
    casts.record("fused_dequantize", "dispatch_bwd", qg.data.size)
    g_rows = _dequantize_nocount(qg, jnp.bfloat16)
    seg = jnp.where(row_map >= 0, row_map, T)
    gx = jax.ops.segment_sum(g_rows.astype(jnp.float32), seg,
                             num_segments=T + 1)[:T]
    return gx.astype(wit.dtype), None


dispatch_quantize.defvjp(_dq_fwd, _dq_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0, 3, 4))
def fp8_dispatch_naive(recipe: Recipe, x, row_map, T: int, ep_axis: str):
    """naive_fp8 (Fig 2c): Q -> permute -> all-to-all(FP8) -> DQ, with a BF16
    backward all-to-all (DeepSeek keeps combine & all backward comm in BF16).
    Two explicit casts — exactly the Q/DQ-around-comm pair of Table 1."""
    y, _ = _fdn_fwd(recipe, x, row_map, T, ep_axis)
    return y


def _a2a(t, axis_name):
    if axis_name is None:           # local EP=1 path (no mesh axis mapped)
        return t
    EP = jax.lax.axis_size(axis_name)
    shp = t.shape
    t = t.reshape(EP, shp[0] // EP, *shp[1:])
    t = jax.lax.all_to_all(t, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)
    # tiled=False with split size 1: (EP, 1, C, ...) -> squeeze
    return t.reshape(shp)


def _fdn_fwd(recipe, x, row_map, T, ep_axis):
    q = quantize_rowwise(x, scale_mode=recipe.scale_mode, tag="q_entry")
    d, s = _permute_pad_fields(q.data, q.scale, row_map, recipe.use_pallas)
    d = _a2a(d, ep_axis)
    s = _a2a(s, ep_axis)
    x_recv = dequantize_exit(recipe, QTensor(d, s, q.tile))
    return x_recv, (row_map, jnp.zeros((0,), x.dtype))


def _fdn_bwd(recipe, T, ep_axis, res, g):
    row_map, wit = res
    g = _a2a(g.astype(jnp.bfloat16), ep_axis)                # BF16 backward comm
    seg = jnp.where(row_map >= 0, row_map, T)
    gx = jax.ops.segment_sum(g.astype(jnp.float32), seg, num_segments=T + 1)[:T]
    return gx.astype(wit.dtype), None


fp8_dispatch_naive.defvjp(_fdn_fwd, _fdn_bwd)


# ---------------------------------------------------------------------------
# The full MoE block (runs inside shard_map; ep_axis must be a mesh axis).
# ---------------------------------------------------------------------------
def moe_block(recipe: Recipe, cfg: MoEConfig, x, w_router, w13, w2):
    """x: (T, D) local tokens.  w13: (E_loc, D, 2F); w2: (E_loc, F, D);
    w_router: (D, E_total) replicated.  Returns (y (T, D), metrics dict).

    ep_axis=None runs the block fully locally (EP=1, every collective an
    identity) — used when the whole train step is already inside a
    data-parallel shard_map (repro.dist) and no expert axis exists."""
    T, D = x.shape
    EP = jax.lax.axis_size(cfg.ep_axis) if cfg.ep_axis is not None else 1
    E_loc = cfg.n_experts // EP
    assert E_loc * EP == cfg.n_experts, (cfg.n_experts, EP)
    k = cfg.top_k
    C_send = _round_up(max(int(T * k / EP * cfg.capacity_factor), 8), 8)
    R = EP * C_send
    # fp8 recipes need 128-row alignment per expert group (transpose blocks
    # and MXU tiles); bf16 only needs sublane alignment.
    C_exp = _round_up(max(R // E_loc, 8), 128 if recipe.is_fp8 else 8)

    with stage_annotation("router"):
        p, ids, aux = router_topk(x, w_router, k)
        row_map_send, slot_expert, slot_assign, drop_frac = _dispatch_plan(
            ids, k, EP, E_loc, C_send)

    # ---- dispatch ----------------------------------------------------------
    with stage_annotation("dispatch"):
        if recipe.name == "fp8_flow":
            q_send = dispatch_quantize(recipe, x, row_map_send, T)
            record_entry_stats("q_entry_moe", x, scale_mode=recipe.scale_mode)
            d = _a2a(q_send.data, cfg.ep_axis)
            s = _a2a(q_send.scale, cfg.ep_axis)
            q_recv = QTensor(d, s, q_send.tile)
            recv_in = q_recv
        elif recipe.name == "naive_fp8":
            recv_in = fp8_dispatch_naive(recipe, x, row_map_send, T,
                                         cfg.ep_axis)
        else:  # bf16 / blockwise: BF16 dispatch
            x_send = _take_rows(x.astype(jnp.bfloat16), row_map_send)
            recv_in = _a2a(x_send, cfg.ep_axis)

        # metadata rides int32/f32 all-to-alls (ids are sent alongside
        # payloads; DeepEP packs them into the same message — we count their
        # bytes too)
        recv_expert = _a2a(slot_expert, cfg.ep_axis)
        p_flat = jnp.where(slot_assign >= 0,
                           p.reshape(-1)[jnp.maximum(slot_assign, 0)], 0.0)
        recv_p = _a2a(p_flat, cfg.ep_axis)

    # ---- expert grouping (fused permute+pad #2) ----------------------------
    with stage_annotation("expert"):
        row_map_exp, ret_map = _expert_plan(recv_expert, E_loc, C_exp)
        if recipe.name == "fp8_flow":
            q_exp = permute_q(recipe, recv_in, row_map_exp, ret_map)
            ffn_in = QTensor(q_exp.data.reshape(E_loc, C_exp, D),
                             q_exp.scale.reshape(E_loc, C_exp, D // TILE),
                             (1, 1, TILE))
        else:
            x_exp = _take_rows(recv_in, row_map_exp)
            ffn_in = x_exp.reshape(E_loc, C_exp, D)

        # ---- grouped expert FFN (the recipe heart) -------------------------
        masked_m = _masked_m_or_none(recipe, row_map_exp, E_loc, C_exp)
        y_exp = tag_saveable(
            expert_ffn(recipe, cfg.act, cfg.dp_axes, (), ffn_in, w13, w2,
                       masked_m),
            "stage_expert_out")

        # expert-side prob weighting (grad wrt p flows through this product)
        p_exp = _take_rows(recv_p[:, None], row_map_exp).reshape(E_loc, C_exp)
        y_exp = y_exp * p_exp[..., None].astype(y_exp.dtype)

    # ---- return + combine (BF16 by design: top-k reduction) ----------------
    with stage_annotation("combine"):
        y_ret = _take_rows(y_exp.reshape(E_loc * C_exp, D), ret_map)
        y_back = _a2a(y_ret, cfg.ep_axis)                    # (R, D) bf16
        seg = jnp.where(row_map_send >= 0, row_map_send, T)
        y = jax.ops.segment_sum(y_back.astype(jnp.float32), seg,
                                num_segments=T + 1)[:T]
    metrics = {"aux_loss": aux, "drop_frac": drop_frac}
    return y.astype(x.dtype), metrics


def moe_block_tp(recipe: Recipe, cfg: MoEConfig, x, w_router, w13, w2,
                 tp_axis: str = "model", combine_mode: str = "local_first"):
    """TP-sharded experts (n_experts < EP, e.g. grok-1's 8 experts on a
    16-wide model axis): every rank holds ALL experts with d_ff sharded.
    No dispatch all-to-all; tokens are grouped locally, each rank computes
    its F-slice, and the second GEMM's partial sums reduce over tp_axis.
    The FP8 pathway (quantize-once, direct-transpose Wgrad, fused ops) is
    unchanged — only the communication pattern differs (psum vs all-to-all).

    combine_mode (the §Perf hillclimb lever for the collective term):
      'psum_first'   paper-naive ordering: all-reduce the FULL (E, C_exp, D)
                     expert outputs, then combine locally.
      'local_first'  combine (segment-sum) the capacity-padded rows down to
                     (T, D) FIRST, then all-reduce only token rows —
                     E*C_exp/T = top_k*cf x fewer bytes on the wire.
      'reduce_scatter' local_first + psum_scatter: the output leaves seq-
                     sharded over tp_axis (Megatron-SP style), another tp x
                     fewer bytes; the caller re-gathers lazily (the residual
                     stream is SP-sharded anyway).
    """
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C_exp = _round_up(max(int(T * k / E * cfg.capacity_factor), 8),
                      128 if recipe.is_fp8 else 8)

    p, ids, aux = router_topk(x, w_router, k)
    # local grouping: assignments -> (E, C_exp) slots
    row_map, slot_expert, slot_assign, drop_frac = _dispatch_plan(
        ids, k, 1, E, E * C_exp)
    # _dispatch_plan with EP=1 gives one big group ordered by expert
    row_map_exp, ret_map = _expert_plan(slot_expert, E, C_exp)
    # compose maps: expert slot -> send slot -> token row
    tok_of_slot = jnp.where(row_map_exp >= 0,
                            row_map[jnp.maximum(row_map_exp, 0)], -1)

    if recipe.name == "fp8_flow":
        q_exp = dispatch_quantize(recipe, x, tok_of_slot, T)
        record_entry_stats("q_entry_moe", x, scale_mode=recipe.scale_mode)
        ffn_in = QTensor(q_exp.data.reshape(E, C_exp, D),
                         q_exp.scale.reshape(E, C_exp, D // TILE), (1, 1, TILE))
    else:
        ffn_in = _take_rows(x.astype(jnp.bfloat16), tok_of_slot)
        ffn_in = ffn_in.reshape(E, C_exp, D)

    masked_m = _masked_m_or_none(recipe, row_map_exp, E, C_exp)
    y_exp = tag_saveable(expert_ffn(recipe, cfg.act, cfg.dp_axes, (tp_axis,),
                                    ffn_in, w13, w2,
                                    masked_m),               # F-sliced partial
                         "stage_expert_out")
    if combine_mode == "psum_first":
        y_exp = jax.lax.psum(y_exp, tp_axis)                 # TP reduction

    p_of_slot = jnp.where(slot_assign >= 0,
                          p.reshape(-1)[jnp.maximum(slot_assign, 0)], 0.0)
    p_exp = _take_rows(p_of_slot[:, None], row_map_exp).reshape(E, C_exp)
    y_exp = y_exp * p_exp[..., None].astype(y_exp.dtype)

    seg = jnp.where(tok_of_slot >= 0, tok_of_slot, T)
    y = jax.ops.segment_sum(
        y_exp.reshape(E * C_exp, D).astype(jnp.float32), seg,
        num_segments=T + 1)[:T]
    if combine_mode == "local_first":
        y = jax.lax.psum(y.astype(jnp.bfloat16), tp_axis)
    elif combine_mode == "reduce_scatter":
        y = jax.lax.psum_scatter(y.astype(jnp.bfloat16), tp_axis,
                                 scatter_dimension=0, tiled=True)
    return y.astype(x.dtype), {"aux_loss": aux, "drop_frac": drop_frac}


# ---------------------------------------------------------------------------
# Decode-time EP MoE as a STAGED program (router -> dispatch -> expert FFN ->
# combine; the layer-stage names of models/lm.py map 1:1 onto these
# functions).  The combine is a psum over ep_axis (vLLM-style EP serving —
# no all-to-all for tiny batches), and the staged decomposition is what lets
# _decode_pipeline double-buffer it: chunk c's psum is on the wire while
# chunk c+1 runs its router/dispatch/expert stages.
# ---------------------------------------------------------------------------
def decode_stage_router(recipe: Recipe, cfg: MoEConfig, x, w_router, r,
                        E_loc: int):
    """Stage 'router' (decode, whole batch): top-k routing + the local-
    assignment map + the block's ONE entry quantize (fp8 recipes).  Routing
    the full batch here keeps aux_loss identical at any pipeline depth."""
    p, ids, aux = router_topk(x, w_router, cfg.top_k)
    local = (ids // E_loc) == r                     # (T, k) mine?
    local_e = jnp.where(local, ids % E_loc, -1).reshape(-1)   # (T*k,)
    if recipe.is_fp8:
        # W8A8 serving path: quantize activations once; weights quantized in
        # the grouped GEMM (forward-only, no backward dataflow concerns).
        # Chunks slice the QTensor — row scales are row-local, so pipeline
        # depth never re-quantizes.
        xq = quantize_rowwise(x, scale_mode=recipe.scale_mode, tag="q_entry")
    else:
        xq = x.astype(jnp.bfloat16)
    return p, aux, local_e, xq


def decode_stage_dispatch(recipe: Recipe, cfg: MoEConfig, xq, local_e_c,
                          tok0: int, E_loc: int, C_dec: int):
    """Stage 'dispatch' (decode, one chunk): expert-slot plan + the local
    gather into the (E_loc, C_dec, D) grouped layout.  Returns (ffn_in,
    row_map_exp, tok_of_slot [chunk-local], n_valid, n_kept)."""
    D = cfg.d_model
    row_map_exp, _ = _expert_plan(local_e_c, E_loc, C_dec)
    tok_loc = jnp.where(row_map_exp >= 0, row_map_exp // cfg.top_k, -1)
    tok_glob = jnp.where(tok_loc >= 0, tok_loc + tok0, -1)
    if recipe.is_fp8:
        d = _take_rows(xq.data, tok_glob)
        s = _take_rows(xq.scale, tok_glob, fill=1.0)
        ffn_in = QTensor(d.reshape(E_loc, C_dec, D),
                         s.reshape(E_loc, C_dec, D // TILE), (1, 1, TILE))
    else:
        ffn_in = _take_rows(xq, tok_glob).reshape(E_loc, C_dec, D)
    n_valid = jnp.sum((local_e_c >= 0).astype(jnp.float32))
    n_kept = jnp.sum((row_map_exp >= 0).astype(jnp.float32))
    return ffn_in, row_map_exp, tok_loc, n_valid, n_kept


def decode_stage_expert(recipe: Recipe, cfg: MoEConfig, ffn_in, w13, w2,
                        p_c, row_map_exp, tok_loc, Tc: int):
    """Stage 'expert FFN' (decode, one chunk): grouped FFN + prob weighting
    + the LOCAL half of the combine (per-token segment sum).  The returned
    (Tc, D) f32 partial still needs the cross-rank psum (stage 'combine')."""
    D = cfg.d_model
    grouped = ffn_in.data if isinstance(ffn_in, QTensor) else ffn_in
    E_loc, C_dec = grouped.shape[0], grouped.shape[1]
    masked_m = _masked_m_or_none(recipe, row_map_exp, E_loc, C_dec)
    y_exp = expert_ffn(recipe, cfg.act, (), (), ffn_in, w13, w2, masked_m)
    p_of_slot = jnp.where(
        row_map_exp >= 0,
        p_c.reshape(-1)[jnp.maximum(row_map_exp, 0)], 0.0)
    y_exp = y_exp * p_of_slot.reshape(E_loc, C_dec)[..., None].astype(
        y_exp.dtype)
    seg = jnp.where(tok_loc >= 0, tok_loc, Tc)
    return jax.ops.segment_sum(
        y_exp.reshape(E_loc * C_dec, D).astype(jnp.float32), seg,
        num_segments=Tc + 1)[:Tc]


def _decode_pipeline(recipe: Recipe, cfg: MoEConfig, x, w_router, w13, w2,
                     n_chunks: int):
    """The staged decode program at pipeline depth n_chunks: chunk c-1's
    combine psum is ISSUED before chunk c's dispatch/expert stages are
    traced, so the collective is on the wire while the independent FFN
    compute runs (decode tokens never interact below the combine — chunking
    the batch is exact, modulo per-chunk capacity C_dec under overflow)."""
    T, D = x.shape
    EP = jax.lax.axis_size(cfg.ep_axis)
    E_loc = cfg.n_experts // EP
    r = jax.lax.axis_index(cfg.ep_axis)
    k = cfg.top_k
    # divisor-of-T clamping lives in ONE place (DispatchPlan), same as the
    # train-side moe_block_overlapped
    n = DispatchPlan(decode_chunks=n_chunks,
                     min_decode_tokens=1).decode_chunks_for(T)
    Tc = T // n
    C_dec = _round_up(max(int(2.0 * Tc * k / cfg.n_experts), 8), 8)

    with stage_annotation("router"):
        p, aux, local_e, xq = decode_stage_router(recipe, cfg, x, w_router,
                                                  r, E_loc)

    def partial(c):
        le = jax.lax.slice_in_dim(local_e, c * Tc * k, (c + 1) * Tc * k)
        with stage_annotation("dispatch"):
            ffn_in, rme, tok_loc, nv, nk = decode_stage_dispatch(
                recipe, cfg, xq, le, c * Tc, E_loc, C_dec)
        pc = jax.lax.slice_in_dim(p, c * Tc, (c + 1) * Tc)
        with stage_annotation("expert"):
            y_loc = decode_stage_expert(recipe, cfg, ffn_in, w13, w2, pc,
                                        rme, tok_loc, Tc)
        return y_loc, nv - nk

    ys = []
    pend_y, drops = partial(0)
    for c in range(1, n):
        # stage 'combine' of chunk c-1 rides the wire while chunk c's
        # dispatch + expert stages (traced next, independent of it) compute
        with stage_annotation("combine"):
            y_prev = jax.lax.psum(pend_y, cfg.ep_axis)
        pend_y, d_c = partial(c)
        ys.append(y_prev)
        drops = drops + d_c
    with stage_annotation("combine"):
        ys.append(jax.lax.psum(pend_y, cfg.ep_axis))
    # real drop accounting: each assignment is local to exactly one rank, so
    # the ones that did not get an expert slot (C_dec overflow) are the
    # drops; summed over the EP group against the global count T*k.
    drop_frac = jax.lax.psum(drops, cfg.ep_axis) / (T * k)
    y = jnp.concatenate(ys, axis=0) if n > 1 else ys[0]
    return y.astype(x.dtype), {"aux_loss": aux, "drop_frac": drop_frac}


def moe_block_decode(recipe: Recipe, cfg: MoEConfig, x, w_router, w13, w2):
    """Decode-time EP MoE: the token batch is small (<= a few hundred) and
    REPLICATED across the ep_axis; each rank computes only its resident
    experts' tokens and the combine is a psum over ep_axis (vLLM-style EP
    serving — no all-to-all for tiny batches).  Forward-only (serving).
    Single synchronous combine (= the staged pipeline at depth 1)."""
    return _decode_pipeline(recipe, cfg, x, w_router, w13, w2, n_chunks=1)


def moe_block_decode_overlapped(recipe: Recipe, cfg: MoEConfig, x, w_router,
                                w13, w2, n_chunks: int = 2):
    """Prefetching decode MoE: the staged pipeline at depth n_chunks — the
    next chunk's router output is consumed (dispatch gather + expert FFN)
    while the previous chunk's combine psum is in flight, converting the
    block's synchronous psum into a double-buffered chain.  Per-token math
    is identical to moe_block_decode when no capacity drops occur (C_dec is
    per-chunk, so drop SETS can differ under overflow, and the chunk-sized
    grouped-GEMM shape can wobble the bf16 output by 1 ulp)."""
    return _decode_pipeline(recipe, cfg, x, w_router, w13, w2,
                            n_chunks=n_chunks)


# ---------------------------------------------------------------------------
# Overlapped EP dispatch: chunked all-to-all / expert-FFN pipeline.
#
# The synchronous moe_block exposes its entire dispatch+combine communication
# on the critical path of every MoE layer.  moe_block_overlapped splits the
# token block into n_chunks micro-chunks and software-pipelines them: chunk
# i's dispatch all-to-all is issued BEFORE chunk i-1's grouped expert FFN, so
# XLA's latency-hiding scheduler can run the collective concurrently with the
# independent FFN compute (rtp-llm DeepEPLowLatencyRouter-style double
# buffering, mapped onto shard_map + lax collectives).
#
# Two further changes vs the synchronous block:
#   * the FP8 payload, its po2 scales, AND the routing metadata (local expert
#     ids + router probs) are PACKED INTO ONE uint8 message per chunk, so the
#     per-chunk dispatch costs 1 collective launch instead of 3;
#   * quantization stays block-level: ONE entry quantize over the full token
#     block (chunks slice the QTensor — row-tile scales are row-local, so no
#     chunk boundary ever re-quantizes) and ONE backward island quantize over
#     the full FFN-output cotangent.  The Fig.-2 cast count is therefore
#     unchanged: still 2 explicit casts for fp8_flow at any n_chunks.
#
# Numerics match moe_block up to f32 accumulation order PROVIDED no capacity
# drops occur (capacities C_send/C_exp are per-chunk, so drop SETS can differ
# between the chunked and monolithic blocks under overflow).
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DispatchPlan:
    """Static overlap configuration threaded through models/lm.py.

    n_chunks          pipeline depth per MoE layer (1 = fused-message only)
    min_chunk_tokens  never chunk below this many local tokens per chunk
                      (tiny chunks waste collective latency on padding)
    decode_chunks     pipeline depth for the decode-path EP MoE (the psum
                      chain of moe_block_decode_overlapped); 1 keeps the
                      synchronous combine
    min_decode_tokens decode batches are small — don't pipeline below this
    """
    n_chunks: int = 2
    min_chunk_tokens: int = 64
    decode_chunks: int = 2
    min_decode_tokens: int = 8

    def chunks_for(self, T: int) -> int:
        cap = max(1, min(self.n_chunks, T // max(self.min_chunk_tokens, 1)))
        return max(d for d in range(1, cap + 1) if T % d == 0)

    def decode_chunks_for(self, T: int) -> int:
        cap = max(1, min(self.decode_chunks,
                         T // max(self.min_decode_tokens, 1)))
        return max(d for d in range(1, cap + 1) if T % d == 0)


def _u8(x):
    """Bitcast to uint8 and flatten the trailing byte axis: (R, ...) -> (R, w)."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return u.reshape(x.shape[0], -1)


def _pack_dispatch_msg(d, s, se, pf):
    """Fuse one chunk's dispatch into a single uint8 message:
    d (R, D) e4m3 payload | s (R, D/TILE) f32 scales | se (R,) i32 local
    expert ids | pf (R,) f32 router probs.  Width D + 4*D/TILE + 8 bytes."""
    return jnp.concatenate(
        [_u8(d), _u8(s), _u8(se[:, None]), _u8(pf[:, None])], axis=1)


def _unpack_dispatch_msg(msg, D: int):
    R = msg.shape[0]
    Ds = D // TILE
    d = jax.lax.bitcast_convert_type(msg[:, :D], jnp.float8_e4m3fn)
    o = D
    s = jax.lax.bitcast_convert_type(
        msg[:, o:o + 4 * Ds].reshape(R, Ds, 4), jnp.float32)
    o += 4 * Ds
    se = jax.lax.bitcast_convert_type(
        msg[:, o:o + 4].reshape(R, 1, 4), jnp.int32)[:, 0]
    pf = jax.lax.bitcast_convert_type(
        msg[:, o + 4:o + 8].reshape(R, 1, 4), jnp.float32)[:, 0]
    return d, s, se, pf


def _pack_bwd_msg(gd, gs, gp):
    """Backward fused message: FP8 input-gradient payload + scales + the
    per-row router-prob gradient ride ONE reverse collective."""
    return jnp.concatenate([_u8(gd), _u8(gs), _u8(gp[:, None])], axis=1)


def _unpack_bwd_msg(msg, D: int):
    R = msg.shape[0]
    Ds = D // TILE
    gd = jax.lax.bitcast_convert_type(msg[:, :D], jnp.float8_e4m3fn)
    gs = jax.lax.bitcast_convert_type(
        msg[:, D:D + 4 * Ds].reshape(R, Ds, 4), jnp.float32)
    gp = jax.lax.bitcast_convert_type(
        msg[:, D + 4 * Ds:].reshape(R, 1, 4), jnp.float32)[:, 0]
    return gd, gs, gp


def _chunk_geometry(recipe, cfg, T: int, n: int, EP: int, E_loc: int):
    Tc = T // n
    k = cfg.top_k
    C_send = _round_up(max(int(Tc * k / EP * cfg.capacity_factor), 8), 8)
    R = EP * C_send
    C_exp = _round_up(max(R // E_loc, 8), 128 if recipe.is_fp8 else 8)
    return Tc, C_send, R, C_exp


def moe_block_overlapped(recipe: Recipe, cfg: MoEConfig, x, w_router, w13, w2,
                         n_chunks: int = 2):
    """Drop-in replacement for moe_block with the chunked/overlapped dispatch
    pipeline.  Same signature + returns, plus the static n_chunks knob
    (clamped to a divisor of the local token count)."""
    T, D = x.shape
    n = DispatchPlan(n_chunks=n_chunks, min_chunk_tokens=1).chunks_for(T)
    p, ids, aux = router_topk(x, w_router, cfg.top_k)
    if recipe.name == "fp8_flow":
        record_entry_stats("q_entry_moe", x, scale_mode=recipe.scale_mode)
        y, drop = _overlap_core_flow(recipe, cfg, n, x, p, ids, w13, w2)
    else:
        y, drop = _overlap_chunks_autodiff(recipe, cfg, n, x, p, ids, w13, w2)
    return y, {"aux_loss": aux, "drop_frac": drop}


def _overlap_chunks_autodiff(recipe, cfg, n, x, p, ids, w13, w2):
    """bf16 / blockwise / naive_fp8: chunked pipeline built from the existing
    autodiff'd primitives.  Chunks are issued back-to-back so independent
    chunks can overlap, but each keeps its recipe's Q/DQ-at-the-boundary
    structure (the fused-message + hoisted-cast pipeline is fp8_flow-only:
    for the baselines, per-chunk casts ARE the cost the paper counts)."""
    T, D = x.shape
    EP = jax.lax.axis_size(cfg.ep_axis)
    E_loc = cfg.n_experts // EP
    k = cfg.top_k
    Tc, C_send, R, C_exp = _chunk_geometry(recipe, cfg, T, n, EP, E_loc)
    ys, drops = [], []
    for c in range(n):
        xc = jax.lax.slice_in_dim(x, c * Tc, (c + 1) * Tc)
        pc = jax.lax.slice_in_dim(p, c * Tc, (c + 1) * Tc)
        idc = jax.lax.slice_in_dim(ids, c * Tc, (c + 1) * Tc)
        rms, se, sa, dc = _dispatch_plan(idc, k, EP, E_loc, C_send)
        if recipe.name == "naive_fp8":
            recv_in = fp8_dispatch_naive(recipe, xc, rms, Tc, cfg.ep_axis)
        else:
            recv_in = _a2a(_take_rows(xc.astype(jnp.bfloat16), rms),
                           cfg.ep_axis)
        recv_expert = _a2a(se, cfg.ep_axis)
        pf = jnp.where(sa >= 0, pc.reshape(-1)[jnp.maximum(sa, 0)], 0.0)
        recv_p = _a2a(pf, cfg.ep_axis)
        rme, ret = _expert_plan(recv_expert, E_loc, C_exp)
        x_exp = _take_rows(recv_in, rme).reshape(E_loc, C_exp, D)
        y_exp = tag_saveable(
            expert_ffn(recipe, cfg.act, cfg.dp_axes, (), x_exp, w13, w2),
            "stage_expert_out")
        p_exp = _take_rows(recv_p[:, None], rme).reshape(E_loc, C_exp)
        y_exp = y_exp * p_exp[..., None].astype(y_exp.dtype)
        y_ret = _take_rows(y_exp.reshape(E_loc * C_exp, D), ret)
        y_back = _a2a(y_ret, cfg.ep_axis)
        seg = jnp.where(rms >= 0, rms, Tc)
        ys.append(jax.ops.segment_sum(y_back.astype(jnp.float32), seg,
                                      num_segments=Tc + 1)[:Tc])
        drops.append(dc)
    y = jnp.concatenate(ys, axis=0).astype(x.dtype)
    return y, jnp.mean(jnp.stack(drops))


def _flow_fwd_impl(recipe, cfg, n, x, p, ids, w13, w2):
    T, D = x.shape
    EP = jax.lax.axis_size(cfg.ep_axis)
    E_loc = cfg.n_experts // EP
    assert E_loc * EP == cfg.n_experts, (cfg.n_experts, EP)
    k = cfg.top_k
    Tc, C_send, R, C_exp = _chunk_geometry(recipe, cfg, T, n, EP, E_loc)

    qw13, qw2 = _quant_weights(recipe, w13, w2)

    # ONE entry quantize for the WHOLE block (the counted forward cast);
    # chunks slice the QTensor — row-tile scales are row-local, so chunk
    # boundaries never re-quantize.
    q = quantize_rowwise(x, scale_mode=recipe.scale_mode, tag="q_entry")

    plans = [_dispatch_plan(jax.lax.slice_in_dim(ids, c * Tc, (c + 1) * Tc),
                            k, EP, E_loc, C_send) for c in range(n)]

    def issue_dispatch(c):
        rms, se, sa, _ = plans[c]
        gmap = jnp.where(rms >= 0, rms + c * Tc, -1)
        d, s = _permute_pad_fields(q.data, q.scale, gmap, recipe.use_pallas)
        pc = jax.lax.slice_in_dim(p, c * Tc, (c + 1) * Tc)
        pf = jnp.where(sa >= 0, pc.reshape(-1)[jnp.maximum(sa, 0)], 0.0)
        return _a2a(_pack_dispatch_msg(d, s, se, pf), cfg.ep_axis)

    recv = issue_dispatch(0)
    ys, saved = [], []
    for c in range(n):
        # double buffer: chunk c+1's fused dispatch is ON THE WIRE while
        # chunk c runs its grouped FFN + combine below
        nxt = issue_dispatch(c + 1) if c + 1 < n else None
        d_r, s_r, e_r, p_r = _unpack_dispatch_msg(recv, D)
        rme, ret = _expert_plan(e_r, E_loc, C_exp)
        d_e, s_e = _permute_pad_fields(d_r, s_r, rme, recipe.use_pallas)
        qx_c = QTensor(d_e.reshape(E_loc, C_exp, D),
                       s_e.reshape(E_loc, C_exp, D // TILE), (1, 1, TILE))
        mm_c = _masked_m_or_none(recipe, rme, E_loc, C_exp)
        y_exp, (qx_c, qa_c, h_c) = ffn_fwd_fp8_core(recipe, cfg.act, qx_c,
                                                    qw13, qw2, mm_c)
        y_exp = tag_saveable(y_exp, "stage_expert_out")
        p_exp = _take_rows(p_r[:, None], rme).reshape(E_loc, C_exp)
        y_w = y_exp * p_exp[..., None].astype(y_exp.dtype)
        y_ret = _take_rows(y_w.reshape(E_loc * C_exp, D), ret)
        y_back = _a2a(y_ret, cfg.ep_axis)        # overlaps chunk c+1's FFN
        rms = plans[c][0]
        seg = jnp.where(rms >= 0, rms, Tc)
        ys.append(jax.ops.segment_sum(y_back.astype(jnp.float32), seg,
                                      num_segments=Tc + 1)[:Tc])
        saved.append((rms, plans[c][2], rme, ret, qx_c, qa_c, h_c, p_exp,
                      y_exp, mm_c))
        recv = nxt
    y = jnp.concatenate(ys, axis=0).astype(x.dtype)
    drop = jnp.mean(jnp.stack([pl[3] for pl in plans]))
    wit = (jnp.zeros((0,), x.dtype), jnp.zeros((0,), w13.dtype),
           jnp.zeros((0,), w2.dtype))
    return (y, drop), (tuple(saved), qw13, qw2, wit)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _overlap_core_flow(recipe: Recipe, cfg: MoEConfig, n: int, x, p, ids,
                       w13, w2):
    """fp8_flow overlapped core with a HAND-WRITTEN backward pipeline that
    mirrors the forward: chunked reverse combine (bf16), ONE hoisted island
    quantize, then per-chunk FFN backward with the FP8 input-gradient +
    prob-gradient riding one fused reverse collective per chunk."""
    (y, drop), _ = _flow_fwd_impl(recipe, cfg, n, x, p, ids, w13, w2)
    return y, drop


def _ocf_fwd(recipe, cfg, n, x, p, ids, w13, w2):
    return _flow_fwd_impl(recipe, cfg, n, x, p, ids, w13, w2)


def _ocf_bwd(recipe, cfg, n, res, ct):
    g_y, _g_drop = ct
    saved, qw13, qw2, (wx, wit13, wit2) = res
    T, D = g_y.shape
    Tc = T // n
    k = cfg.top_k
    E_loc, C_exp, _ = saved[0][4].data.shape
    S = E_loc * C_exp

    # ---- stage 1: per-chunk reverse combine (bf16 collectives pipeline) ----
    g_yexp, g_pexp = [], []
    for c in range(n):
        rms, sa, rme, ret, qx_c, qa_c, h_c, p_exp, y_exp, mm_c = saved[c]
        g_c = jax.lax.slice_in_dim(g_y, c * Tc, (c + 1) * Tc)
        g_back = _take_rows(g_c.astype(jnp.float32), rms)     # (R, D)
        g_ret = _a2a(g_back.astype(jnp.bfloat16), cfg.ep_axis)
        g_yw = _take_rows(g_ret, rme).reshape(E_loc, C_exp, D)
        g_yexp.append(g_yw * p_exp[..., None].astype(g_yw.dtype))
        g_pexp.append(jnp.sum(g_yw.astype(jnp.float32)
                              * y_exp.astype(jnp.float32), axis=-1))

    # ---- the ONE explicit backward cast (BF16 island -> FP8), hoisted out
    # of the chunk loop: quantize(concat) == concat(quantize) for row tiles,
    # so no chunk boundary re-quantizes and the Fig.-2 count stays at 2.
    qg_all = _q_row(recipe, jnp.concatenate(g_yexp, axis=1), "q_bwd_island")

    # ---- stage 2: per-chunk FFN backward + fused reverse dispatch,
    # software-pipelined (chunk c's reverse a2a flies while chunk c+1's FFN
    # backward computes; its unpack + segment-sums happen one step later).
    wg13 = jnp.zeros((), jnp.float32)
    wg2 = jnp.zeros((), jnp.float32)
    gx_chunks = [None] * n
    gp_chunks = [None] * n

    def land(c, msg):
        rms, sa = saved[c][0], saved[c][1]
        gd, gs, gp = _unpack_bwd_msg(msg, D)
        casts.record("fused_dequantize", "dispatch_bwd", gd.size)
        g_rows = _dequantize_nocount(QTensor(gd, gs, (1, TILE)), jnp.bfloat16)
        seg = jnp.where(rms >= 0, rms, Tc)
        gx_chunks[c] = jax.ops.segment_sum(
            g_rows.astype(jnp.float32), seg,
            num_segments=Tc + 1)[:Tc].astype(wx.dtype)
        segp = jnp.where(sa >= 0, sa, Tc * k)
        gp_chunks[c] = jax.ops.segment_sum(
            gp.astype(jnp.float32), segp,
            num_segments=Tc * k + 1)[:Tc * k].reshape(Tc, k)

    pending = None
    for c in range(n):
        rms, sa, rme, ret, qx_c, qa_c, h_c, p_exp, y_exp, mm_c = saved[c]
        qg_c = QTensor(
            jax.lax.slice_in_dim(qg_all.data, c * C_exp, (c + 1) * C_exp,
                                 axis=1),
            jax.lax.slice_in_dim(qg_all.scale, c * C_exp, (c + 1) * C_exp,
                                 axis=1), qg_all.tile)
        gxq, wg13_c, wg2_c = ffn_bwd_fp8_core(recipe, cfg.act, (), qx_c, qa_c,
                                              h_c, qw13, qw2, qg_c, mm_c)
        wg13 = wg13 + wg13_c
        wg2 = wg2 + wg2_c
        # inverse expert-grouping permute (FP8-exact), then ONE fused reverse
        # collective: e4m3 payload + po2 scales + router-prob grads together
        gd, gs = _permute_pad_fields(gxq.data.reshape(S, D),
                                     gxq.scale.reshape(S, D // TILE), ret,
                                     recipe.use_pallas)
        gp_r = _take_rows(g_pexp[c].reshape(S, 1), ret)[:, 0]
        msg = _a2a(_pack_bwd_msg(gd, gs, gp_r), cfg.ep_axis)
        if pending is not None:
            land(*pending)
        pending = (c, msg)
    land(*pending)

    g_x = jnp.concatenate(gx_chunks, axis=0)
    g_p = jnp.concatenate(gp_chunks, axis=0)
    wg_axes = cfg.dp_axes
    if wg_axes:
        wg13 = jax.lax.psum(wg13, wg_axes)
        wg2 = jax.lax.psum(wg2, wg_axes)
    return (g_x, g_p, None, wg13.astype(wit13.dtype), wg2.astype(wit2.dtype))


_overlap_core_flow.defvjp(_ocf_fwd, _ocf_bwd)
