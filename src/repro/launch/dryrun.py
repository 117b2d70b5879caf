"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
record memory_analysis / cost_analysis / collective-bytes for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_moe_235b \
      --shape train_4k [--multi-pod] [--recipe fp8_flow] [--out out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every defined cell
"""
# The production mesh needs 512 placeholder devices; jax locks the device
# count at first init, so this MUST precede every other import.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# a CPU lowering tool: pinned to the CPU backend, so on a machine with a TPU
# it neither takes the chip nor fails for want of 512 of them
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402

import jax            # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_arch  # noqa: E402
from repro.configs.base import SHAPES, applicable_shapes  # noqa: E402
from repro.core.recipes import get_recipe  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import sharding  # noqa: E402
from repro.models.lm import init_cache, init_params, ParallelPlan  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.roofline import analysis  # noqa: E402


def opt_config_for(cfg) -> adamw.AdamWConfig:
    """>=100B params: bf16 moments, no separate master (memory plan §4)."""
    big = cfg.n_params() > 100e9
    return adamw.AdamWConfig(
        moment_dtype=jnp.bfloat16 if big else jnp.float32,
        master_weights=not big)


def _env_overrides(cfg):
    """Perf-iteration knobs (EXPERIMENTS.md §Perf): capacity factor and FP8
    KV cache, switchable per dry-run via env."""
    import dataclasses
    cf = os.environ.get("REPRO_CF")
    if cf:
        cfg = dataclasses.replace(cfg, capacity_factor=float(cf))
    return cfg


def fp8_kv() -> bool:
    return os.environ.get("REPRO_FP8_KV", "0") == "1"


def w8_serve() -> bool:
    return os.environ.get("REPRO_W8", "0") == "1"


def input_specs(arch: str, shape_name: str):
    """ShapeDtypeStruct stand-ins for every model input of this cell —
    weak-type-correct, shardable, no device allocation."""
    cfg = _env_overrides(get_arch(arch))
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt)

    if shape.kind == "train":
        A = cfg.grad_accum
        mb = B // A
        S_tok = S - (cfg.frontend_len if cfg.frontend != "none" else 0)
        batch = {
            "tokens": sds((A, mb, S_tok), i32),
            "targets": sds((A, mb, S_tok), i32),
            "mask": sds((A, mb, S_tok), f32),
        }
        if cfg.frontend != "none":
            batch["prefix"] = sds((A, mb, cfg.frontend_len, cfg.d_model), bf16)
        if cfg.encdec:
            batch["enc_input"] = sds((A, mb, S, cfg.d_model), bf16)
        if A == 1:
            batch = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                     for k, v in batch.items()}
        return {"batch": batch}

    if shape.kind == "prefill":
        S_tok = S - (cfg.frontend_len if cfg.frontend != "none" else 0)
        batch = {"tokens": sds((B, S_tok), i32)}
        if cfg.frontend != "none":
            batch["prefix"] = sds((B, cfg.frontend_len, cfg.d_model), bf16)
        if cfg.encdec:
            batch["enc_input"] = sds((B, S, cfg.d_model), bf16)
        return {"batch": batch}

    # decode
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S, fp8_kv=fp8_kv()))
    return {
        "cache": cache,
        "tokens": sds((B, 1), i32),
        "pos": jax.ShapeDtypeStruct((), i32),
    }


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               recipe_name: str = "fp8_flow"):
    """Returns (jitted_fn, example_args_with_shardings, meta)."""
    cfg = _env_overrides(get_arch(arch))
    shape = SHAPES[shape_name]
    recipe = get_recipe(recipe_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = sharding.make_plan(cfg, mesh)
    n_chips = 512 if multi_pod else 256

    params_shapes = jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0)))
    if shape.kind == "decode" and w8_serve():
        # W8-resident serving: pre-quantized FP8 weights, no FSDP gathers
        import dataclasses as _dc
        from repro.serve.w8 import quantize_params_for_serving
        params_shapes = jax.eval_shape(quantize_params_for_serving,
                                       params_shapes)
        cfg_specs = _dc.replace(cfg, fsdp=False)
        plan = _dc.replace(plan, fsdp_axis=None)
        params_sh = sharding.tree_specs(cfg_specs, mesh, params_shapes)
    else:
        params_sh = sharding.tree_specs(cfg, mesh, params_shapes)
    ins = input_specs(arch, shape_name)

    if shape.kind == "train":
        opt = opt_config_for(cfg)
        opt_shapes = jax.eval_shape(
            lambda ps: adamw.init_state(opt, ps), params_shapes)
        opt_sh = sharding.opt_state_specs(cfg, mesh, params_sh, opt_shapes)
        state_shapes = {"params": params_shapes, "opt": opt_shapes}
        state_sh = {"params": params_sh, "opt": opt_sh}
        batch_sh = sharding.batch_specs(mesh, ins["batch"], plan.dp_axes)
        from repro.train.train_step import make_train_step
        step = make_train_step(cfg, recipe, plan, opt,
                               grad_accum=cfg.grad_accum)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     donate_argnums=(0,))
        args = (state_shapes, ins["batch"])
        tokens = shape.global_batch * shape.seq_len
        mf = analysis.model_flops_train(cfg, tokens)
    elif shape.kind == "prefill":
        batch_sh = sharding.batch_specs(mesh, ins["batch"], plan.dp_axes)
        from repro.serve.serve_step import make_prefill
        step = make_prefill(cfg, recipe, plan)
        fn = jax.jit(step, in_shardings=(params_sh, batch_sh))
        args = (params_shapes, ins["batch"])
        tokens = shape.global_batch * shape.seq_len
        mf = analysis.model_flops_decode(cfg, tokens)
    else:
        cache_sh = sharding.cache_specs(cfg, mesh, ins["cache"], plan.dp_axes)
        tok_sh = sharding.batch_specs(mesh, {"tokens": ins["tokens"]},
                                      plan.dp_axes)["tokens"]
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.serve.serve_step import make_serve_step
        step = make_serve_step(cfg, recipe, plan)
        fn = jax.jit(step, in_shardings=(params_sh, cache_sh, tok_sh,
                                         NamedSharding(mesh, P())),
                     donate_argnums=(1,))
        args = (params_shapes, ins["cache"], ins["tokens"], ins["pos"])
        mf = analysis.model_flops_decode(cfg, shape.global_batch)

    meta = {"arch": arch, "shape": shape_name, "recipe": recipe_name,
            "multi_pod": multi_pod, "n_chips": n_chips,
            "model_flops_global": mf, "mesh": dict(mesh.shape)}
    return fn, args, meta, mesh


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             recipe_name: str = "fp8_flow", verbose: bool = True,
             probe: bool = True):
    t0 = time.time()
    fn, args, meta, mesh = build_cell(arch, shape_name, multi_pod=multi_pod,
                                      recipe_name=recipe_name)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    if probe:
        # trip-count-correct roofline via component probes (probe.py)
        from repro.roofline import probe as probe_mod
        cfg = _env_overrides(get_arch(arch))
        shape = SHAPES[shape_name]
        plan = sharding.make_plan(cfg, mesh)
        params_shapes = jax.eval_shape(
            lambda: init_params(cfg, jax.random.key(0)))
        if shape.kind == "decode" and w8_serve():
            import dataclasses as _dc
            from repro.serve.w8 import quantize_params_for_serving
            params_shapes = jax.eval_shape(quantize_params_for_serving,
                                           params_shapes)
            plan = _dc.replace(plan, fsdp_axis=None)
            cfg = _dc.replace(cfg, fsdp=False)
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            cost = probe_mod.probe_train(cfg, get_recipe(recipe_name), plan,
                                         mesh, params_shapes,
                                         B // cfg.grad_accum, S)
        else:
            cost = probe_mod.probe_infer(cfg, get_recipe(recipe_name), plan,
                                         mesh, params_shapes, B, S,
                                         decode=shape.kind == "decode")
        roof = analysis.Roofline(
            flops=cost["flops"], hbm_bytes=cost["hbm_bytes"],
            coll_bytes=cost["coll_bytes"], coll_by_kind=cost["coll_by_kind"],
            model_flops=meta["model_flops_global"] / meta["n_chips"],
            n_chips=meta["n_chips"])
    else:
        roof = analysis.analyze(
            compiled, model_flops_global=meta["model_flops_global"],
            n_chips=meta["n_chips"])
    rec = dict(meta)
    rec.update({
        "ok": True,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_est": mem.argument_size_in_bytes
            + mem.output_size_in_bytes + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "roofline": roof.to_dict(),
    })
    if verbose:
        m = rec["memory"]
        r = rec["roofline"]
        print(f"[dryrun] {arch} x {shape_name} x "
              f"{'2x16x16' if multi_pod else '16x16'} ({recipe_name}): "
              f"args={m['argument_bytes']/2**30:.2f}GiB "
              f"temp={m['temp_bytes']/2**30:.2f}GiB "
              f"peak~{m['peak_bytes_est']/2**30:.2f}GiB | "
              f"t_comp={r['t_compute']*1e3:.1f}ms "
              f"t_mem={r['t_memory']*1e3:.1f}ms "
              f"t_coll={r['t_collective']*1e3:.1f}ms "
              f"bottleneck={r['bottleneck']} mfu={r['mfu']:.2%} "
              f"({rec['compile_s']}s compile)")
    return rec


def all_cells():
    for arch in ARCH_IDS:
        cfg = get_arch(arch)
        for shape_name in applicable_shapes(cfg):
            yield arch, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--recipe", default="fp8_flow")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]
    records = []
    for arch, shape_name in cells:
        for mp in meshes:
            try:
                records.append(run_cell(arch, shape_name, multi_pod=mp,
                                        recipe_name=args.recipe))
            except Exception as e:  # noqa: BLE001 — record and continue
                traceback.print_exc()
                records.append({"arch": arch, "shape": shape_name,
                                "multi_pod": mp, "recipe": args.recipe,
                                "ok": False, "error": f"{type(e).__name__}: {e}"})
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"[dryrun] wrote {len(records)} records -> {args.out}")
    n_ok = sum(1 for r in records if r.get("ok"))
    print(f"[dryrun] {n_ok}/{len(records)} cells compiled")
    return 0 if n_ok == len(records) else 1


if __name__ == "__main__":
    raise SystemExit(main())
