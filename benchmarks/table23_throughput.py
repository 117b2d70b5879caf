"""Table 2/3 reproduction (modeled): per-recipe roofline step time -> TGS
(tokens/chip/s) for the paper's MoE workload on the single-pod mesh.

Wall-clock TGS cannot be measured on this CPU container; the modeled TGS is
max(compute, memory, collective) roofline time from the trip-count-correct
component probes (roofline/probe.py), per recipe — reproducing the paper's
ORDERING (fp8_flow > blockwise ~ bf16) and the mechanism (fewer cast ops +
FP8 wire bytes).  Reads cached sweep results when present; probing all
recipes live takes ~4 x 60 s of XLA compilation on this machine, so the
default target is the paper-scale-but-fits v2-lite config; set
REPRO_T23_ARCH=qwen3_moe_235b for the big one.
"""
from __future__ import annotations

import os

from benchmarks.common import emit

ARCH = os.environ.get("REPRO_T23_ARCH", "deepseek_v2_lite")
RECIPES = ["bf16", "blockwise", "naive_fp8", "fp8_flow"]


def run():
    # needs the 512-virtual-device mesh; jax may already be initialized with
    # 1 device in this process -> re-exec the probe loop in a subprocess.
    # The child is a CPU lowering tool pinned to the CPU backend: a parent
    # that holds the chip never has a child that needs it.
    import subprocess
    import sys
    if os.environ.get("_REPRO_T23_CHILD") != "1":
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=512")
        env["JAX_PLATFORMS"] = "cpu"
        env["_REPRO_T23_CHILD"] = "1"
        env.setdefault("PYTHONPATH", "src:.")
        out = subprocess.run(
            [sys.executable, "-c",
             "from benchmarks import table23_throughput as m; m.run()"],
            env=env, capture_output=True, text=True, timeout=3000)
        sys.stdout.write(out.stdout)
        if out.returncode != 0:
            sys.stderr.write(out.stderr[-2000:])
            raise RuntimeError("table23 child failed")
        return
    import jax
    from repro.configs import get_arch
    from repro.configs.base import SHAPES
    from repro.core.recipes import get_recipe
    from repro.launch.mesh import make_production_mesh
    from repro.launch.sharding import make_plan
    from repro.models.lm import init_params
    from repro.roofline import probe as probe_mod
    from repro.roofline.analysis import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

    cfg = get_arch(ARCH)
    shape = SHAPES["train_4k"]
    mesh = make_production_mesh(multi_pod=False)
    plan = make_plan(cfg, mesh)
    params_shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    tokens = shape.global_batch * shape.seq_len

    results = {}
    for name in RECIPES:
        recipe = get_recipe(name)
        cost = probe_mod.probe_train(cfg, recipe, plan, mesh, params_shapes,
                                     shape.global_batch // cfg.grad_accum,
                                     shape.seq_len)
        t = max(cost["flops"] / PEAK_FLOPS_BF16,
                cost["hbm_bytes"] / HBM_BW,
                cost["coll_bytes"] / ICI_BW)
        results[name] = (t, cost)
        tgs = tokens / t / 256
        emit(f"table23_{ARCH}_{name}", t * 1e6,
             f"modeled_TGS={tgs:.0f};"
             f"t_comp_ms={cost['flops'] / PEAK_FLOPS_BF16 * 1e3:.1f};"
             f"t_mem_ms={cost['hbm_bytes'] / HBM_BW * 1e3:.1f};"
             f"t_coll_ms={cost['coll_bytes'] / ICI_BW * 1e3:.1f}")
        jax.clear_caches()

    base = results["bf16"][0]
    for name in RECIPES[1:]:
        emit(f"table23_{ARCH}_speedup_{name}", 0.0,
             f"vs_bf16={base / results[name][0]:.3f}x")


if __name__ == "__main__":
    run()
