"""The jitted training step: loss -> grad -> (optional grad-accum) ->
gradient reduction -> AdamW update.

`make_train_step` closes over static config (arch, recipe, plan, optimizer)
and returns a function (state, batch) -> (state, metrics) suitable for
jax.jit with explicit in/out shardings (launch/sharding.py).

Two reduction regimes:
  dist=None      the legacy implicit path — the batch is sharded over the DP
                 axes and pjit inserts f32 psums for the gradients.
  dist=DistPlan  the explicit FP8-native wire (repro.dist): the whole step
                 runs inside ONE shard_map over the DP axis; gradients
                 reduce-scatter as e4m3 payload + po2 int8 exponents packed
                 into one uint8 message per bucket (pre-agreed scales, no
                 double quantization error), the ZeRO-1 owned shard updates
                 FP8-split optimizer state, and the updated bf16 param
                 shards all-gather back.  Sensitive leaves (norms, router,
                 embeddings) ride a bf16 psum.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import quant
from repro.core.recipes import Recipe
from repro.models.lm import ParallelPlan, forward
from repro.obs.trace import annotate, stage_annotation
from repro.optim import adamw, schedules
from repro.train import guards


def make_train_step(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan,
                    opt: adamw.AdamWConfig, *, grad_accum: int = 1,
                    dist: Optional[Any] = None,
                    total_steps: int = 100_000, warmup_steps: int = 100,
                    guard: Optional[guards.GuardPlan] = None):
    """Returns train_step(state, batch) -> (state, metrics).

    state = {'params', 'opt': adamw state (or dist state when dist is set)}
    batch = {'tokens' (B, S), 'targets', 'mask', ...} with B the
    PER-MICROBATCH size when grad_accum > 1 — the step loops microbatches
    via lax.scan over the leading accum axis of the batch.

    dist: an active repro.dist.DistPlan routes the step through the
    quantized ZeRO-1 wire (see _make_dist_train_step).

    guard: a train/guards.py GuardPlan arms in-step anomaly detection —
    the step carries state['guard'] (grad-norm EMA), collects FP8
    quantize-site stats, guards the DP wire, and emits a 'guard_flags'
    uint32 in the metrics.  guard=None leaves the traced step bitwise
    identical to an unguarded build (the detection code never traces)."""
    if dist is not None and dist.active:
        return _make_dist_train_step(cfg, recipe, plan, opt, dist,
                                     grad_accum=grad_accum,
                                     total_steps=total_steps,
                                     warmup_steps=warmup_steps, guard=guard)

    def loss_fn(params, mb):
        loss, metrics = forward(cfg, recipe, plan, params, mb)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        ctx = quant.collect_stats() if guard is not None \
            else contextlib.nullcontext()
        with ctx:
            loss, metrics, grads = _local_grads(loss_fn, params, batch,
                                                grad_accum)
        lr_scale = schedules.warmup_cosine(
            state["opt"]["step"], total_steps=total_steps,
            warmup_steps=warmup_steps)
        with stage_annotation("optimizer"):
            new_params, new_opt, opt_metrics = adamw.apply_updates(
                opt, params, grads, state["opt"], lr_scale=lr_scale)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        new_state = {"params": new_params, "opt": new_opt}
        if guard is not None:
            flags, new_g, gm = guards.evaluate(
                guard, state["guard"], loss=loss,
                gnorm=opt_metrics["grad_norm"],
                sat_frac=metrics.get("quant_sat_frac"),
                flush_frac=metrics.get("quant_flush_frac"))
            new_state["guard"] = new_g
            metrics.update(gm)
        return new_state, metrics

    return train_step


def _local_grads(loss_fn, params, batch, grad_accum):
    """value+grad, optionally scanning a leading grad-accum batch axis.
    Forward metrics are accumulated across microbatches and averaged, the
    same way the loss is — they used to be silently dropped."""
    if grad_accum > 1:
        def acc_body(carry, mb):
            gsum, lsum = carry
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            gsum = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (gsum, lsum + loss), metrics

        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (gsum, lsum), ms = jax.lax.scan(acc_body, (g0, jnp.float32(0.0)),
                                        batch)
        grads = jax.tree.map(lambda g: g / grad_accum, gsum)
        metrics = jax.tree.map(lambda a: jnp.mean(a, axis=0), ms)
        return lsum / grad_accum, metrics, grads
    (loss, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, batch)
    return loss, metrics, grads


# ---------------------------------------------------------------------------
# The staged layer program's streaming step: manual per-layer forward (each
# layer's vjp saved), then a reverse-order backward that quantizes and
# reduce-scatters each layer's gradient bucket(s) AS SOON AS that layer's
# vjp has produced them — the DP wire rides behind the remaining backward
# compute instead of waiting for all of it (DistPlan schedule='stream').
# ---------------------------------------------------------------------------
def _streamed_grads(cfg, recipe, lplan, params, batch, layout, axis, n_dp,
                    wire, grad_accum: int = 1, guard=None):
    """Returns (loss, metrics, owned, sens_done, sens_raw, wire_bad):

    owned      aligns with layout.buckets (the layered, reverse-layer-order
               layout) and holds each bucket's already-reduced f32 shard;
    sens_done  maps a STACK-TAGGED sensitive leaf's path to its fully
               reduced, restacked f32 gradient — each layer's slice was
               issued on the bf16 fallback wire together with that layer's
               FP8 bucket(s), from inside the backward;
    sens_raw   maps the remaining (non-stacked: embeddings, final norms,
               head) sensitive leaves' flatten indices to their local
               gradients, reduced by the caller post-hoc as before.

    Rematerialization composes through the MemoryPlan (train/memory.py):
    each per-block jax.vjp wraps its layers per cfg.remat_policy ('pair'
    coarsens the streaming granularity to two-layer blocks).

    grad_accum > 1 streams too: the batch carries a leading microbatch
    axis; every microbatch's bucket flats and sensitive slices accumulate
    LOCALLY, and each quantize + reduce-scatter (and each bf16 psum) is
    issued exactly once, from inside the LAST microbatch's backward — the
    wire still hides behind backward compute, and the pre-agreed scales see
    the full accumulated gradient (no per-microbatch quantization)."""
    from repro.dist import grad_comm
    from repro.dist.plan import bucket_flat_parts, path_str
    from repro.models.layers import apply_norm
    from repro.models.lm import (AUX_LOSS_COEF, _embed_tokens, _lm_logits,
                                 _xent, iter_layer_slices, layer_forward)
    from repro.train.memory import MemoryPlan

    mem = MemoryPlan.from_config(cfg)

    # static maps: full-tree flatten index -> position in each stack's
    # per-layer subtree flatten order (subtree traversal is the same sorted
    # dict walk, so relative order matches)
    flatpaths = jax.tree_util.tree_flatten_with_path(params)[0]
    by_path = {path_str(pth): i for i, (pth, _) in enumerate(flatpaths)}
    stack_pos = {}
    for s in ("dense_layers", "layers"):
        idxs = [i for i, (pth, _) in enumerate(flatpaths)
                if path_str(pth).split(".")[0] == s]
        stack_pos[s] = {i: j for j, i in enumerate(idxs)}
    layer_buckets = {}
    for bi, b in enumerate(layout.buckets):
        layer_buckets.setdefault((b.stack, b.layer), []).append((bi, b))
    sens_stacked = {s.index: s for s in layout.sensitive
                    if s.stack is not None}
    sens_other_idx = {s.index for s in layout.sensitive if s.stack is None}
    entries = list(iter_layer_slices(cfg, params))
    blocks = mem.blocks_of(entries)

    owned = [None] * len(layout.buckets)
    flat_acc = [None] * len(layout.buckets)  # local microbatch accumulation
    sens_layer_acc = {}             # (index, layer) -> local grad sum
    sens_done_parts = {}            # index -> {layer: REDUCED grad slice}
    sens_raw = {}                   # index -> local (accumulated) gradient
    loss_sum = jnp.float32(0.0)
    aux_sum = jnp.float32(0.0)
    armed = quant.stats_armed()     # guard stats threaded through each vjp
    wire_bad = jnp.bool_(False) if guard is not None else None

    for m in range(grad_accum):
        mb = batch if grad_accum == 1 else \
            jax.tree.map(lambda a, _m=m: a[_m], batch)
        emit = m == grad_accum - 1
        inv = 1.0 if grad_accum == 1 else 1.0 / grad_accum
        tokens, targets = mb["tokens"], mb["targets"]
        mask = mb.get("mask", jnp.ones_like(tokens, jnp.float32))

        # ---- staged forward (unrolled; the two-layer carry window defers
        # each block's scalar epilogue past the next block's issue) --------
        x, emb_vjp = jax.vjp(
            lambda e: _embed_tokens(cfg, {"embed": e}, tokens),
            params["embed"])
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        recs = []                   # (block entries, vjp) in forward order
        aux_total = jnp.float32(0.0)
        pending = None
        for blk in blocks:
            ps = tuple(e[4] for e in blk)

            def f(ps_, xc, _km=tuple((e[2], e[3]) for e in blk)):
                a_blk = jnp.float32(0.0)
                for p, (kind, moe) in zip(ps_, _km):
                    xc, a = layer_forward(cfg, recipe, lplan, kind, moe, p,
                                          xc, positions)
                    a_blk = a_blk + a
                if armed:   # guard stats: drained in-block, threaded out
                    return xc, a_blk, quant.drain_stats()
                return xc, a_blk

            if armed:
                (x, a, sv), vjp_b = jax.vjp(mem.wrap(f), ps, x)
                quant.reinject_stats(sv)
            else:
                (x, a), vjp_b = jax.vjp(mem.wrap(f), ps, x)
            recs.append((blk, vjp_b))
            if pending is not None:
                aux_total = aux_total + pending
            pending = a
        if pending is not None:
            aux_total = aux_total + pending

        hp = {"final_norm_s": params["final_norm_s"]}
        if "final_norm_b" in params:
            hp["final_norm_b"] = params["final_norm_b"]
        hp["embed" if cfg.tie_embeddings else "lm_head"] = \
            params["embed"] if cfg.tie_embeddings else params["lm_head"]

        def head_f(hp_, xf, _targets=targets, _mask=mask):
            xn = apply_norm(cfg.norm, xf,
                            {"final_norm_s": hp_["final_norm_s"],
                             "final_norm_b": hp_.get("final_norm_b")},
                            "final_norm")
            return _xent(_lm_logits(cfg, hp_, xn, None), _targets, _mask)

        xent_loss, head_vjp = jax.vjp(head_f, hp, x)
        loss_sum = loss_sum + xent_loss + AUX_LOSS_COEF * aux_total
        aux_sum = aux_sum + aux_total

        # ---- streaming backward: reverse layer order, wire-on-the-way ----
        g_hp, g_x = head_vjp(jnp.float32(1.0))
        g_aux = jnp.float32(AUX_LOSS_COEF)      # d loss / d aux_l
        for blk, vjp_b in reversed(recs):
            if armed:   # zero cotangent for the threaded stats output
                g_ps, g_x = vjp_b((g_x, g_aux, quant.zero_stats()))
            else:
                g_ps, g_x = vjp_b((g_x, g_aux))
            for (stack, l, _k, _mo, _p), g_pl in zip(reversed(blk),
                                                     reversed(g_ps)):
                g_leaves = jax.tree.leaves(g_pl)
                pos = stack_pos[stack]
                for bi, b in layer_buckets.get((stack, l), ()):
                    flat = bucket_flat_parts(
                        b, lambda s: g_leaves[pos[s.index]])
                    if flat_acc[bi] is not None:
                        flat = flat + flat_acc[bi]
                    if emit:
                        # issued HERE, between layer l's and layer l-1's
                        # backward GEMMs: the pre-agreed-scale quantize +
                        # single-uint8-message RS (of the microbatch MEAN)
                        flat_m = flat * inv if grad_accum > 1 else flat
                        with annotate(f"wire/bucket{bi}_{stack}_l{l}"):
                            if guard is not None:
                                owned[bi], bad = \
                                    grad_comm.reduce_scatter_bucket(
                                        flat_m, axis, n_dp, wire,
                                        guard=guard)
                                wire_bad = jnp.logical_or(wire_bad, bad)
                            else:
                                owned[bi] = grad_comm.reduce_scatter_bucket(
                                    flat_m, axis, n_dp, wire)
                        flat_acc[bi] = None
                    else:
                        flat_acc[bi] = flat
                for i in pos:
                    g_s = g_leaves[pos[i]]
                    if i in sens_stacked:
                        key = (i, l)
                        if key in sens_layer_acc:
                            g_s = g_s + sens_layer_acc[key]
                        if emit:
                            # the layer's bf16 psum rides with its bucket(s)
                            with annotate(f"wire/sensitive_{stack}_l{l}"):
                                sens_done_parts.setdefault(i, {})[l] = \
                                    grad_comm.reduce_sensitive(
                                        g_s * inv if grad_accum > 1
                                        else g_s, axis, n_dp, wire)
                            sens_layer_acc.pop(key, None)
                        else:
                            sens_layer_acc[key] = g_s
                    elif i in sens_other_idx:   # non-layered fallback leaf
                        sens_raw[i] = g_s if i not in sens_raw \
                            else sens_raw[i] + g_s

        g_embed = emb_vjp(g_x)[0]
        if cfg.tie_embeddings:
            g_embed = g_embed + g_hp["embed"].astype(g_embed.dtype)
        ends = {"embed": g_embed,
                "final_norm_s": g_hp["final_norm_s"]}
        if "final_norm_b" in by_path:
            ends["final_norm_b"] = g_hp["final_norm_b"]
        if not cfg.tie_embeddings:
            ends["lm_head"] = g_hp["lm_head"]
        for name, g in ends.items():
            i = by_path[name]
            sens_raw[i] = g if i not in sens_raw else sens_raw[i] + g

    if grad_accum > 1:
        sens_raw = {i: g / grad_accum for i, g in sens_raw.items()}
    sens_done = {
        sens_stacked[i].path: jnp.stack([pieces[l]
                                         for l in range(len(pieces))])
        for i, pieces in sens_done_parts.items()}
    loss = loss_sum / grad_accum
    metrics = {"aux_loss": aux_sum / grad_accum, "loss": loss}
    if armed:
        # final drain: per-block reinjects + the dp_wire quantize records
        sv = quant.drain_stats()
        sm = quant.site_maxima(sv)
        metrics["quant_sat_frac"] = sm[0]
        metrics["quant_flush_frac"] = sm[1]
        metrics["quant_site_stats"] = sv
    return loss, metrics, owned, sens_done, sens_raw, wire_bad


# ---------------------------------------------------------------------------
# The explicit FP8 wire + ZeRO-1 step (repro.dist).
# ---------------------------------------------------------------------------
def _make_dist_train_step(cfg: ArchConfig, recipe: Recipe, plan: ParallelPlan,
                          opt: adamw.AdamWConfig, dist, *, grad_accum: int,
                          total_steps: int, warmup_steps: int, guard=None):
    from jax.sharding import PartitionSpec as P
    from repro.dist import grad_comm
    from repro.dist import opt_state as ost
    from repro.dist.plan import (bucket_flat, bucket_scatter, build_layout,
                                 streaming_fallback_reason)

    mesh = plan.mesh
    if mesh is None or dist.axis not in mesh.axis_names:
        raise ValueError(f"DistPlan needs a plan.mesh with axis "
                         f"'{dist.axis}'; got {mesh}")
    if dist.schedule == "stream":
        reason = streaming_fallback_reason(cfg, grad_accum=grad_accum)
        if reason:
            raise ValueError(
                f"DistPlan schedule='stream' cannot run: {reason} — use "
                f"schedule='posthoc' (launch/train.py falls back "
                f"automatically)")
    n_dp = mesh.shape[dist.axis]
    nontrivial = [a for a in mesh.axis_names
                  if a != dist.axis and mesh.shape[a] != 1]
    if nontrivial:
        raise ValueError(
            f"the DistPlan wire runs the forward replica-locally inside a "
            f"shard_map over '{dist.axis}'; model-parallel axes {nontrivial} "
            f"cannot nest another shard_map on jax {jax.__version__} — use "
            f"dist=None (implicit pjit psum) on model-parallel meshes")
    if dist.shard_multiple % n_dp != 0:
        raise ValueError(
            f"DP size {n_dp} does not divide DistPlan.shard_multiple="
            f"{dist.shard_multiple}: bucket rows pad to shard_multiple, so "
            f"ZeRO-1 shards would be unequal — set shard_multiple to a "
            f"multiple of the DP size (or size the data axis to a divisor)")
    # the forward must not open a nested shard_map: run it replica-local
    local_plan = dataclasses.replace(plan, mesh=None, dp_axes=(),
                                     fsdp_axis=None, shard_map_mlp=False,
                                     moe_overlap=None)
    pol = dist.policy
    axis = dist.axis

    def loss_fn(params, mb):
        loss, metrics = forward(cfg, recipe, local_plan, params, mb)
        return loss, metrics

    def train_step(state, batch):
        params = state["params"]
        layout = build_layout(params, dist)     # static (shapes only)
        treedef = jax.tree.structure(params)
        if dist.schedule == "stream":
            reason = streaming_fallback_reason(cfg, layout, grad_accum)
            if reason:
                raise ValueError(
                    f"DistPlan schedule='stream' cannot run: {reason}")

        def body_impl(params, opt_st, batch, gstate):
            pleaves = treedef.flatten_up_to(params)
            wire_bad = None
            if dist.schedule == "stream":
                # staged layer program: per-layer backward, bucket i's
                # quantize + reduce-scatter issued the moment layer i's
                # grads exist (reverse layer order) — the DP wire hides
                # behind the remaining backward compute.  Stack-tagged
                # sensitive leaves stream per layer on the bf16 wire;
                # grad_accum > 1 accumulates locally and wires once on the
                # last microbatch.
                loss, fwd_metrics, owned, sens_done, sens_raw, wire_bad = \
                    _streamed_grads(cfg, recipe, local_plan, params, batch,
                                    layout, axis, n_dp, dist.wire,
                                    grad_accum=grad_accum, guard=guard)
            else:
                loss, fwd_metrics, grads = _local_grads(
                    loss_fn, params, batch, grad_accum)
                gleaves = treedef.flatten_up_to(grads)

                # quantized reduce-scatter: one fused uint8 message per
                # bucket, scales pre-agreed (scale_sync) so the sum never
                # re-quantizes
                if guard is not None:
                    pairs = [grad_comm.reduce_scatter_bucket(
                        bucket_flat(b, gleaves), axis, n_dp, dist.wire,
                        guard=guard) for b in layout.buckets]
                    owned = [o for o, _ in pairs]
                    wire_bad = jnp.bool_(False)
                    for _, bad in pairs:
                        wire_bad = jnp.logical_or(wire_bad, bad)
                    # wire-quantize stats recorded during the RS, after
                    # forward() drained its own: merge the site matrices
                    wire_sv = quant.drain_stats()
                    fwd_metrics = dict(fwd_metrics)
                    sites = jnp.maximum(
                        fwd_metrics["quant_site_stats"], wire_sv)
                    sm = quant.site_maxima(sites)
                    fwd_metrics["quant_site_stats"] = sites
                    fwd_metrics["quant_sat_frac"] = sm[0]
                    fwd_metrics["quant_flush_frac"] = sm[1]
                else:
                    owned = [grad_comm.reduce_scatter_bucket(
                        bucket_flat(b, gleaves), axis, n_dp, dist.wire)
                        for b in layout.buckets]
                sens_raw = {i: gleaves[i] for i, _ in layout.sensitive}
                sens_done = {}
            sens_g = {p: sens_done[p] if p in sens_done
                      else grad_comm.reduce_sensitive(sens_raw[i], axis,
                                                      n_dp, dist.wire)
                      for i, p in layout.sensitive}

            # global grad norm in one fused f32 scalar pass: each replica
            # owns disjoint shards, so psum(sum owned^2) is the exact total
            parts = [jnp.sum(jnp.square(o)) for o in owned]
            sq_owned = jnp.sum(jnp.stack(parts)) if parts \
                else jnp.float32(0.0)
            sq_owned = jax.lax.psum(sq_owned, axis)
            sq_sens = [jnp.sum(jnp.square(g)) for g in sens_g.values()]
            gnorm = jnp.sqrt(sq_owned + (jnp.sum(jnp.stack(sq_sens))
                                         if sq_sens else jnp.float32(0.0)))
            with stage_annotation("optimizer"):
                clip = adamw.clip_factor(opt, gnorm)
                step = opt_st["step"] + 1
                b1c, b2c = adamw.bias_corrections(opt, step)
                lr = opt.lr * schedules.warmup_cosine(
                    opt_st["step"], total_steps=total_steps,
                    warmup_steps=warmup_steps)

                # ZeRO-1: update the owned shard, all-gather bf16 param
                # shards
                new_leaves, stacked_new, new_flat = {}, {}, []
                for b, o_g, st_b in zip(layout.buckets, owned,
                                        opt_st["flat"]):
                    shard32 = None
                    if "master" not in st_b:
                        rows_l = b.rows // n_dp
                        idx = jax.lax.axis_index(axis)
                        # flatten in the (bf16) param dtype, not f32: only the
                        # owned 1/P shard is widened (fp8-class leaves are all
                        # low-precision unless the user inits f32 params)
                        fdt = jnp.float32 if any(
                            pleaves[s.index].dtype == jnp.float32
                            for s in b.slots) else jnp.bfloat16
                        shard32 = jax.lax.dynamic_slice_in_dim(
                            bucket_flat(b, pleaves, fdt), idx * rows_l,
                            rows_l, 0).astype(jnp.float32)
                    new_shard, new_st = ost.flat_bucket_update(
                        opt, pol, st_b, o_g, clip, lr, b1c, b2c, shard32)
                    full = grad_comm.all_gather_shard(new_shard, axis)
                    for key, piece in bucket_scatter(b, full, pleaves).items():
                        if isinstance(key, tuple):  # layered: (index, layer)
                            stacked_new.setdefault(key[0], {})[key[1]] = piece
                        else:
                            new_leaves[key] = piece
                    new_flat.append(new_st)
                # layered buckets update one layer slice at a time; restack
                for i, pieces in stacked_new.items():
                    new_leaves[i] = jnp.stack(
                        [pieces[l] for l in range(pleaves[i].shape[0])])

                # sensitive leaves: replicated classic update (f32 state)
                sens_st = opt_st["sens"]
                new_sens = {"m": {}, "v": {}}
                if "master" in sens_st:
                    new_sens["master"] = {}
                for i, pth in layout.sensitive:
                    p = pleaves[i]
                    g32 = sens_g[pth] * clip
                    base = sens_st["master"][pth] if "master" in sens_st \
                        else p.astype(jnp.float32)
                    new_master, m_new, v_new = adamw.adamw_math(
                        opt, g32, sens_st["m"][pth], sens_st["v"][pth], base,
                        lr, b1c, b2c)
                    new_leaves[i] = new_master.astype(p.dtype)
                    new_sens["m"][pth] = m_new
                    new_sens["v"][pth] = v_new
                    if "master" in sens_st:
                        new_sens["master"][pth] = new_master

                new_params = jax.tree.unflatten(
                    treedef, [new_leaves[i] for i in range(len(pleaves))])
                new_opt = {"step": step, "flat": tuple(new_flat),
                           "sens": new_sens}
            # quant_* stats reduce by pmax (an anomaly ANYWHERE must trip
            # the replica-uniform flag); everything else stays pmean
            metrics = {k: jax.lax.pmax(v, axis) if k.startswith("quant_")
                       else jax.lax.pmean(v, axis)
                       for k, v in dict(fwd_metrics).items()}
            metrics["loss"] = jax.lax.pmean(loss, axis)
            metrics["grad_norm"] = gnorm
            metrics["lr"] = lr
            if guard is None:
                return new_params, new_opt, metrics
            # all evaluate() inputs are replica-uniform (pmean/psum/pmax
            # above; wire_anomaly pmaxes internally), so flags and the new
            # guard state replicate for free under out_specs P()
            flags, new_g, gm = guards.evaluate(
                guard, gstate, loss=metrics["loss"], gnorm=gnorm,
                sat_frac=metrics.get("quant_sat_frac"),
                flush_frac=metrics.get("quant_flush_frac"),
                wire_bad=wire_bad)
            metrics.update(gm)
            return new_params, new_opt, metrics, new_g

        lead = 1 if grad_accum > 1 else 0
        batch_specs = jax.tree.map(
            lambda a: P(*((None,) * lead + (axis,))), batch)
        opt_in = {"step": P(),
                  "flat": tuple(P(axis, None) for _ in layout.buckets),
                  "sens": P()}
        # check_vma=False: the body differentiates the replicated params
        # into per-replica gradients and reduces them on its own wire.  With
        # varying-axes checking, JAX would psum those gradients implicitly
        # (the transpose of replicating an invariant input) before the wire.
        if guard is None:
            def body(params, opt_st, batch):
                return body_impl(params, opt_st, batch, None)

            sm = jax.shard_map(body, mesh=mesh,
                               in_specs=(P(), opt_in, batch_specs),
                               out_specs=(P(), opt_in, P()), check_vma=False)
            new_params, new_opt, metrics = sm(params, state["opt"], batch)
            return {"params": new_params, "opt": new_opt}, metrics

        def body(params, opt_st, batch, gstate):
            with quant.collect_stats():
                return body_impl(params, opt_st, batch, gstate)

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(), opt_in, batch_specs, P()),
                           out_specs=(P(), opt_in, P(), P()), check_vma=False)
        new_params, new_opt, metrics, new_g = sm(
            params, state["opt"], batch, state["guard"])
        return {"params": new_params, "opt": new_opt, "guard": new_g}, \
            metrics

    return train_step


def init_train_state(cfg: ArchConfig, opt: adamw.AdamWConfig, key,
                     dtype=jnp.bfloat16, dist=None,
                     guard: Optional[guards.GuardPlan] = None
                     ) -> Dict[str, Any]:
    from repro.models.lm import init_params
    params = init_params(cfg, key, dtype)
    if dist is not None and dist.active:
        from repro.dist import opt_state as ost
        from repro.dist.plan import build_layout
        layout = build_layout(params, dist)
        state = {"params": params,
                 "opt": ost.init_dist_state(opt, params, layout, dist)}
    else:
        state = {"params": params, "opt": adamw.init_state(opt, params)}
    if guard is not None:
        state["guard"] = guards.init_guard_state()
    return state
