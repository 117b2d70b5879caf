"""Component cost probes — trip-count-correct roofline accounting.

``compiled.cost_analysis()`` on a whole train step counts each ``lax.scan``
(while-loop) body ONCE, so an L-layer model's FLOPs would be undercounted by
~L x.  Instead we lower each repeated component separately at the SAME
shardings as the full step and multiply by its trip count:

  train    = n_groups x group_grad  +  head_loss_grad  +  optimizer_update
  prefill  = n_groups x group_fwd   +  head_logits
  decode   = n_groups x group_decode + head_logits

Every number still comes from a compiled XLA artifact of this cell's exact
shapes/shardings — the full-step compile remains the fit/compile proof; the
probes provide the per-step cost integral.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.roofline import analysis


def _cost_of(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):   # jax 0.4.x: one dict per program
        cost = cost[0] if cost else {}
    coll = analysis.collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "hbm_bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(coll.values())),
        "coll_by_kind": coll,
    }


def _acc(total, part, mult):
    total["flops"] += part["flops"] * mult
    total["hbm_bytes"] += part["hbm_bytes"] * mult
    total["coll_bytes"] += part["coll_bytes"] * mult
    for k, v in part["coll_by_kind"].items():
        total["coll_by_kind"][k] = total["coll_by_kind"].get(k, 0) + v * mult
    return total


def _x_sharding(mesh, plan, B, S):
    import numpy as np
    dp_size = int(np.prod([mesh.shape[a] for a in plan.dp_axes])) \
        if plan.dp_axes else 1
    dp = plan.dp_axes if B % max(dp_size, 1) == 0 else None
    seq = plan.tp_axis if S % mesh.shape[plan.tp_axis] == 0 else None
    return NamedSharding(mesh, P(dp, seq, None))


def _group_slice_shapes(cfg, params_shapes, stack_key="layers"):
    glen = len(cfg.pattern)
    nd = cfg.n_dense_layers if cfg.moe else 0
    n = (cfg.n_layers - nd) if stack_key == "layers" else \
        (nd if stack_key == "dense_layers" else cfg.n_enc_layers)
    if n % glen:
        glen = 1
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((glen,) + a.shape[1:], a.dtype),
        params_shapes[stack_key]), glen, n // glen


def _group_specs(cfg, mesh, slice_shapes):
    from repro.launch.sharding import tree_specs
    return tree_specs(cfg, mesh, slice_shapes)


def probe_train(cfg, recipe, plan, mesh, params_shapes, B, S):
    """Costs for one train step (global batch B x S) on this mesh."""
    from repro.models.lm import _sub_layer, layer_kinds

    total = {"flops": 0.0, "hbm_bytes": 0.0, "coll_bytes": 0.0,
             "coll_by_kind": {}}
    D = cfg.d_model
    x_sds = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)
    x_sh = _x_sharding(mesh, plan, B, S)
    positions = jnp.arange(S, dtype=jnp.int32)

    def make_group_fn(pattern, moe):
        def run(x, pslice):
            aux = jnp.float32(0.0)
            for i in range(len(pattern)):
                pi = jax.tree.map(lambda a: a[i], pslice)
                x, a, _, _, _ = _sub_layer(cfg, recipe, plan, pattern[i],
                                           moe, pi, x, positions)
                aux = aux + a
            return x, aux

        from repro.train.memory import MemoryPlan
        ckpt = MemoryPlan.from_config(cfg).wrap(run)

        def grad_fn(x, pslice):
            (y, aux), vjp = jax.vjp(ckpt, x, pslice)
            gx, gp = vjp((jnp.ones_like(y), jnp.float32(1.0)))
            return gx, gp
        return grad_fn

    # main stack
    slice_shapes, glen, ng = _group_slice_shapes(cfg, params_shapes, "layers")
    pattern = cfg.pattern if len(cfg.pattern) == glen else (cfg.pattern[0],)
    fn = jax.jit(make_group_fn(pattern, cfg.moe),
                 in_shardings=(x_sh, _group_specs(cfg, mesh, slice_shapes)))
    with jax.set_mesh(mesh):
        comp = fn.lower(x_sds, slice_shapes).compile()
    _acc(total, _cost_of(comp), ng * cfg.grad_accum)

    # dense prologue stack
    nd = cfg.n_dense_layers if cfg.moe else 0
    if nd:
        sl, glen_d, ng_d = _group_slice_shapes(cfg, params_shapes,
                                               "dense_layers")
        fn = jax.jit(make_group_fn((cfg.pattern[0],) * glen_d, False),
                     in_shardings=(x_sh, _group_specs(cfg, mesh, sl)))
        with jax.set_mesh(mesh):
            comp = fn.lower(x_sds, sl).compile()
        _acc(total, _cost_of(comp), ng_d * cfg.grad_accum)

    # encoder stack (seamless)
    if cfg.encdec:
        sl, glen_e, ng_e = _group_slice_shapes(cfg, params_shapes,
                                               "enc_layers")
        fn = jax.jit(make_group_fn(("global",) * glen_e, False),
                     in_shardings=(x_sh, _group_specs(cfg, mesh, sl)))
        with jax.set_mesh(mesh):
            comp = fn.lower(x_sds, sl).compile()
        _acc(total, _cost_of(comp), ng_e * cfg.grad_accum)

    # embedding + head + CE (fwd+bwd)
    total = _probe_head(cfg, recipe, plan, mesh, params_shapes, B, S, total,
                        train=True, mult=cfg.grad_accum)
    # optimizer update
    total = _probe_opt(cfg, mesh, params_shapes, total)
    return total


def _probe_head(cfg, recipe, plan, mesh, params_shapes, B, S, total, *,
                train, mult=1):
    from repro.models.lm import _lm_logits, _xent, _embed_tokens
    from repro.launch.sharding import tree_specs

    D = cfg.d_model
    Vp = cfg.vocab_padded
    emb_sds = params_shapes["embed"]
    head_key = "embed" if cfg.tie_embeddings else "lm_head"
    head_sds = params_shapes[head_key]
    sub = {"embed": emb_sds, head_key: head_sds}
    sub_specs = tree_specs(cfg, mesh, sub)
    x_sh = _x_sharding(mesh, plan, B, S)
    tok_sh = NamedSharding(mesh, P(
        plan.dp_axes if B % max(1, _dpsize(mesh, plan)) == 0 else None, None))

    def f(x, params, tokens, targets):
        emb = _embed_tokens(cfg, params, tokens)
        x = x + emb                    # stands in for the residual stream
        logits = _lm_logits(cfg, params, x, plan)
        if train:
            mask = jnp.ones_like(targets, jnp.float32)
            return _xent(logits, targets, mask)
        return jnp.sum(logits[:, -1, :].astype(jnp.float32))

    def g(x, params, tokens, targets):
        if train:
            _, grads = jax.value_and_grad(f, argnums=(0, 1))(
                x, params, tokens, targets)
            return grads
        return f(x, params, tokens, targets)

    x_sds = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)
    t_sds = jax.ShapeDtypeStruct((B, S), jnp.int32)
    fn = jax.jit(g, in_shardings=(x_sh, sub_specs, tok_sh, tok_sh))
    with jax.set_mesh(mesh):
        comp = fn.lower(x_sds, sub, t_sds, t_sds).compile()
    return _acc(total, _cost_of(comp), mult)


def _dpsize(mesh, plan):
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in plan.dp_axes])) \
        if plan.dp_axes else 1


def _probe_opt(cfg, mesh, params_shapes, total):
    from repro.launch.dryrun import opt_config_for
    from repro.launch.sharding import opt_state_specs, tree_specs
    from repro.optim import adamw

    opt = opt_config_for(cfg)
    opt_shapes = jax.eval_shape(lambda ps: adamw.init_state(opt, ps),
                                params_shapes)
    p_specs = tree_specs(cfg, mesh, params_shapes)
    o_specs = opt_state_specs(cfg, mesh, p_specs, opt_shapes)
    g_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), params_shapes)

    def f(params, grads, state):
        return adamw.apply_updates(opt, params, grads, state)[:2]

    fn = jax.jit(f, in_shardings=(p_specs, p_specs, o_specs))
    with jax.set_mesh(mesh):
        comp = fn.lower(params_shapes, g_shapes, opt_shapes).compile()
    return _acc(total, _cost_of(comp), 1)


def probe_infer(cfg, recipe, plan, mesh, params_shapes, B, S, *, decode):
    """Costs for prefill (full fwd) or one decode token."""
    from repro.models.lm import _sub_layer, init_cache

    total = {"flops": 0.0, "hbm_bytes": 0.0, "coll_bytes": 0.0,
             "coll_by_kind": {}}
    D = cfg.d_model

    if not decode:
        x_sds = jax.ShapeDtypeStruct((B, S, D), jnp.bfloat16)
        x_sh = _x_sharding(mesh, plan, B, S)
        positions = jnp.arange(S, dtype=jnp.int32)

        def make_fwd(pattern, moe):
            def run(x, pslice):
                for i in range(len(pattern)):
                    pi = jax.tree.map(lambda a: a[i], pslice)
                    x, _, _, _, _ = _sub_layer(cfg, recipe, plan, pattern[i],
                                               moe, pi, x, positions)
                return x
            return run

        sl, glen, ng = _group_slice_shapes(cfg, params_shapes, "layers")
        pattern = cfg.pattern if len(cfg.pattern) == glen else (cfg.pattern[0],)
        fn = jax.jit(make_fwd(pattern, cfg.moe),
                     in_shardings=(x_sh, _group_specs(cfg, mesh, sl)))
        with jax.set_mesh(mesh):
            comp = fn.lower(x_sds, sl).compile()
        _acc(total, _cost_of(comp), ng)
        nd = cfg.n_dense_layers if cfg.moe else 0
        if nd:
            sl, glen_d, ng_d = _group_slice_shapes(cfg, params_shapes,
                                                   "dense_layers")
            fn = jax.jit(make_fwd((cfg.pattern[0],) * glen_d, False),
                         in_shardings=(x_sh, _group_specs(cfg, mesh, sl)))
            with jax.set_mesh(mesh):
                comp = fn.lower(x_sds, sl).compile()
            _acc(total, _cost_of(comp), ng_d)
        if cfg.encdec:
            sl, glen_e, ng_e = _group_slice_shapes(cfg, params_shapes,
                                                   "enc_layers")
            fn = jax.jit(make_fwd(("global",) * glen_e, False),
                         in_shardings=(x_sh, _group_specs(cfg, mesh, sl)))
            with jax.set_mesh(mesh):
                comp = fn.lower(x_sds, sl).compile()
            _acc(total, _cost_of(comp), ng_e)
        return _probe_head(cfg, recipe, plan, mesh, params_shapes, B, S,
                           total, train=False)

    # decode: one layer group against its cache slice
    from repro.launch.sharding import cache_specs
    from repro.models.lm import decode_step
    # probing per-group decode requires the cache slice machinery; instead
    # lower the FULL decode step and multiply the while-body by the group
    # count analytically is incorrect; so probe one group explicitly:
    from repro.launch.dryrun import fp8_kv
    kinds = [cfg.pattern[i % len(cfg.pattern)] for i in range(cfg.n_layers)]
    sl, glen, ng = _group_slice_shapes(cfg, params_shapes, "layers")
    pattern = cfg.pattern if len(cfg.pattern) == glen else (cfg.pattern[0],)
    cache_shapes = jax.eval_shape(
        lambda: init_cache(cfg, B, S, fp8_kv=fp8_kv()))
    c_specs = cache_specs(cfg, mesh, cache_shapes, plan.dp_axes)

    def grp(x, pslice, kslice, vslice, st, cv, pos):
        from repro.models.lm import _moe_stage, _mlp_decode
        from repro.models.layers import apply_norm, attn_block
        from repro.models.ssm import mamba2_block
        positions = jnp.full((1,), pos, jnp.int32)
        for i in range(len(pattern)):
            pi = jax.tree.map(lambda a: a[i], pslice)
            kind = pattern[i]
            h = apply_norm(cfg.norm, x, pi, "ln1")
            if kind == "ssm":
                mix, _, _ = mamba2_block(cfg, pi, h, state=st[i],
                                         conv_state=cv[i], decode=True)
            elif kind == "hybrid":
                a_out, _ = attn_block(cfg, pi, h, positions=positions,
                                      cache=(kslice[i], vslice[i]),
                                      cache_pos=pos)
                s_out, _, _ = mamba2_block(cfg, pi, h, state=st[i],
                                           conv_state=cv[i], decode=True)
                mix = 0.5 * (a_out + s_out)
            else:
                window = cfg.window if kind == "local" else 0
                mix, _ = attn_block(cfg, pi, h, positions=positions,
                                    layer_window=window,
                                    cache=(kslice[i], vslice[i]),
                                    cache_pos=pos)
            x = x + mix
            if not (kind == "ssm" and not cfg.d_ff):
                h2 = apply_norm(cfg.norm, x, pi, "ln2")
                if cfg.moe:
                    mo, _ = _moe_stage(cfg, recipe, plan, pi, h2, decode=True)
                else:
                    mo = _mlp_decode(cfg, pi, h2)
                x = x + mo
        return x

    x_sds = jax.ShapeDtypeStruct((B, 1, D), jnp.bfloat16)
    x_sh = NamedSharding(mesh, P(
        plan.dp_axes if B % max(1, _dpsize(mesh, plan)) == 0 else None,
        None, None))
    main = cache_shapes.get("main_attn")
    mssm = cache_shapes.get("main_ssm")

    def sl_k(c):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            (glen,) + a.shape[1:], a.dtype), c)

    args = [x_sds, sl]
    in_sh = [x_sh, _group_specs(cfg, mesh, sl)]
    kty = vty = sty = cty = None
    if main is not None:
        kty, vty = sl_k(main["k"]), sl_k(main["v"])
    else:
        kty = vty = jax.ShapeDtypeStruct((glen, 1, 1, 1, 1), jnp.bfloat16)
    if mssm is not None:
        sty, cty = sl_k(mssm["state"]), sl_k(mssm["conv"])
    else:
        sty = cty = jax.ShapeDtypeStruct((glen, 1, 1, 1, 1), jnp.float32)
    cspec = cache_specs(cfg, mesh, {"k": kty, "v": vty}, plan.dp_axes) \
        if main is not None else {
            "k": NamedSharding(mesh, P()), "v": NamedSharding(mesh, P())}
    sspec = cache_specs(cfg, mesh, {"state": sty, "conv": cty}, plan.dp_axes) \
        if mssm is not None else {
            "state": NamedSharding(mesh, P()), "conv": NamedSharding(mesh, P())}
    args += [kty, vty, sty, cty, jax.ShapeDtypeStruct((), jnp.int32)]
    in_sh += [cspec["k"], cspec["v"], sspec["state"], sspec["conv"],
              NamedSharding(mesh, P())]
    fn = jax.jit(grp, in_shardings=tuple(in_sh))
    with jax.set_mesh(mesh):
        comp = fn.lower(*args).compile()
    _acc(total, _cost_of(comp), ng)
    return _probe_head(cfg, recipe, plan, mesh, params_shapes, B, 1, total,
                       train=False)
