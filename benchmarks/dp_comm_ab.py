"""Quantized vs bf16 data-parallel gradient reduction A/B.

The legacy path all-reduces every gradient byte in >=bf16 on the DP axis.
The DistPlan wire (repro.dist) reduce-scatters e4m3 payloads + int8 po2
exponents packed into ONE uint8 message per bucket, with sensitive leaves
(norms/router/embeddings) on a bf16 psum fallback, and all-gathers only the
updated bf16 param shards (ZeRO-1).

This bench verifies the wire for real — it LOWERS the DistPlan train step
on an N-virtual-device CPU mesh and checks the jaxpr: one all_to_all per
bucket, uint8 on the wire, no f32 gradient all-reduce — and reports the
bytes-on-wire model (no TPU fabric on this container; ring factors
(P-1)/P per hop, all-reduce = 2 hops):

  PYTHONPATH=src python benchmarks/dp_comm_ab.py --dry-run     # CI smoke
  PYTHONPATH=src python benchmarks/dp_comm_ab.py --dry-run --overlap
  PYTHONPATH=src python benchmarks/dp_comm_ab.py --devices 8 --steps 3

--overlap additionally lowers the STREAMING schedule (DistPlan
schedule='stream': layer-aligned reverse-order buckets, each quantize +
reduce-scatter issued from inside the staged backward) and checks the
jaxpr for the structural property the schedule exists for: at least one
bucket reduce-scatter appears BEFORE the last backward GEMM (the post-hoc
step issues every one after), plus the modelled exposed-comm delta (greedy
hiding of each bucket's wire time behind the remaining layers' backward
compute).

Acceptance gate (dry-run): the FP8 bucket path moves >= 3x fewer gradient
bytes than a bf16 all-reduce of the same leaves (1.008 B/elem + amax
agreement vs 4 B/elem at P=8 -> ~3.7x).
"""
from __future__ import annotations

import argparse
import os
import sys


def run(devices: int = 8, arch: str = "qwen15_05b", steps: int = 2,
        dry_run: bool = False, overlap: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    try:
        from benchmarks.common import emit, time_fn
    except ModuleNotFoundError:      # invoked as `python benchmarks/...py`
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from benchmarks.common import emit, time_fn
    from repro.launch.mesh import make_mesh
    from repro.configs import get_arch
    from repro.core.fp8 import TILE
    from repro.core.recipes import get_recipe
    from repro.data.pipeline import DataConfig, make_batch
    from repro.dist import DistPlan, build_layout
    from repro.dist.grad_comm import wire_grad_bytes, wire_param_bytes
    from repro.models.lm import ParallelPlan
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import init_train_state, make_train_step

    ndev = jax.device_count()
    if ndev < devices:
        print(f"dp_comm_ab: only {ndev} devices visible (wanted {devices}); "
              f"set XLA_FLAGS=--xla_force_host_platform_device_count=N",
              file=sys.stderr)
        devices = ndev
    P = devices
    mesh = make_mesh((P, 1), ("data", "model"))
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    cfg = get_arch(arch).reduced()
    recipe = get_recipe("fp8_flow")
    opt = AdamWConfig(lr=1e-3)
    data = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=max(P, 8))

    dist_fp8 = DistPlan(wire="fp8")
    state = init_train_state(cfg, opt, jax.random.key(0), dist=dist_fp8)
    layout = build_layout(state["params"], dist_fp8)
    n_fp8 = layout.fp8_elems
    n_all = sum(int(np.prod(l.shape))
                for l in jax.tree.leaves(state["params"]))
    n_sens = n_all - n_fp8

    # ---- real lowering check: the fused uint8 wire must be in the HLO ----
    step = make_train_step(cfg, recipe, plan, opt, dist=dist_fp8,
                           total_steps=100, warmup_steps=5)
    batch = make_batch(data, 0)
    jaxpr = str(jax.make_jaxpr(step)(state, batch))
    n_a2a = jaxpr.count("all_to_all")
    assert n_a2a == len(layout.buckets), \
        f"expected 1 fused all_to_all per bucket, got {n_a2a} " \
        f"vs {len(layout.buckets)} buckets"
    assert "u8[" in jaxpr, "wire message is not uint8-packed"
    with mesh:
        jax.jit(step).lower(state, batch)      # the CI "it lowers" gate

    # ---- bytes-on-wire model --------------------------------------------
    fp8_grad = (sum(wire_grad_bytes(b.rows * TILE, P, "fp8")
                    for b in layout.buckets)
                + wire_grad_bytes(n_sens, P, "bf16", mode="none"))
    bf16_bucket = wire_grad_bytes(n_fp8, P, "bf16", mode="none")
    bf16_all = wire_grad_bytes(n_all, P, "bf16", mode="none")
    bucket_only = sum(wire_grad_bytes(b.rows * TILE, P, "fp8")
                      for b in layout.buckets)
    ratio_bucket = bf16_bucket / max(bucket_only, 1e-9)
    ratio_e2e = bf16_all / max(fp8_grad, 1e-9)
    gather = wire_param_bytes(n_fp8, P)

    emit(f"dp_comm_ab_p{P}_{arch}", 0.0,
         f"fp8_bucket_grad_B={bucket_only:.0f};"
         f"bf16_allreduce_same_leaves_B={bf16_bucket:.0f};"
         f"bucket_ratio={ratio_bucket:.2f}x;"
         f"end_to_end_grad_ratio={ratio_e2e:.2f}x;"
         f"zero1_param_allgather_B={gather:.0f};"
         f"buckets={len(layout.buckets)};fp8_elems={n_fp8};"
         f"sens_elems={n_sens};a2a_ops={n_a2a}",
         units="bytes", kind="model")
    if P > 1:
        assert ratio_bucket >= 3.0, \
            f"FP8 bucket path only {ratio_bucket:.2f}x below bf16 (< 3x)"

    # ---- streaming schedule: lowering + jaxpr interleave + exposed model -
    if overlap:
        from benchmarks.common import ici_model_us
        from repro.dist.grad_comm import stream_exposed_us
        from repro.roofline.analysis import PEAK_FLOPS_FP8

        dist_s = DistPlan(wire="fp8", schedule="stream")
        state_s = init_train_state(cfg, opt, jax.random.key(0), dist=dist_s)
        layout_s = build_layout(state_s["params"], dist_s)
        step_s = make_train_step(cfg, recipe, plan, opt, dist=dist_s,
                                 total_steps=100, warmup_steps=5)
        jx_s = str(jax.make_jaxpr(step_s)(state_s, batch))
        n_a2a_s = jx_s.count("all_to_all")
        with mesh:
            jax.jit(step_s).lower(state_s, batch)   # the "it lowers" gate
        interleaved = 0 <= jx_s.find("all_to_all") < jx_s.rfind("dot_general")
        posthoc_interleaved = 0 <= jaxpr.find("all_to_all") \
            < jaxpr.rfind("dot_general")
        if P > 1:
            assert n_a2a_s == len(layout_s.buckets), (n_a2a_s,
                                                      len(layout_s.buckets))
            assert interleaved, \
                "streaming: no bucket reduce-scatter before the last " \
                "backward GEMM in the jaxpr"
            assert not posthoc_interleaved, \
                "post-hoc baseline unexpectedly interleaved"

        # exposed-comm model: bucket i's wire time hides behind the NEXT
        # layer's backward GEMMs (greedy drain, reverse emission order);
        # per-layer backward ~= 4 flops/param/token on the local shard
        tok_local = data.global_batch * data.seq_len / P
        bucket_us = [ici_model_us(wire_grad_bytes(b.rows * TILE, P, "fp8"))
                     for b in layout_s.buckets]
        bwd_us = [4.0 * sum(s.size for s in b.slots) * tok_local
                  / PEAK_FLOPS_FP8 * 1e6 for b in layout_s.buckets]
        overlap_us = bwd_us[1:] + [0.0]
        exposed_stream = stream_exposed_us(bucket_us, overlap_us)
        exposed_posthoc = sum(bucket_us)
        emit(f"dp_comm_stream_p{P}_{arch}", exposed_stream,
             f"posthoc_exposed_us={exposed_posthoc:.1f};"
             f"stream_exposed_us={exposed_stream:.1f};"
             f"hidden_us={exposed_posthoc - exposed_stream:.1f};"
             f"buckets={len(layout_s.buckets)};a2a_ops={n_a2a_s};"
             f"jaxpr_interleaved={interleaved}",
             units="us", kind="model")
        assert exposed_stream <= exposed_posthoc + 1e-9

    if dry_run:
        extra = " + streaming schedule interleaves in the jaxpr" \
            if overlap else ""
        print(f"dp_comm_ab: dry-run OK (lowered fp8 wire on {P} devices; "
              f"bucket path {ratio_bucket:.2f}x fewer grad bytes than bf16 "
              f"all-reduce, {ratio_e2e:.2f}x end-to-end incl. bf16 "
              f"fallback{extra})")
        return

    # ---- CPU wall-clock A/B (functional check, not a fabric model) -------
    for wire in ("fp8", "f32"):
        d = DistPlan(wire=wire)
        st = init_train_state(cfg, opt, jax.random.key(0), dist=d)
        fn = jax.jit(make_train_step(cfg, recipe, plan, opt, dist=d,
                                     total_steps=100, warmup_steps=5))
        with mesh:
            us = time_fn(lambda s, b: fn(s, b)[1]["loss"], st, batch,
                         iters=steps, warmup=1)
        emit(f"dp_comm_ab_step_{wire}_p{P}", us, "cpu_wall_us_per_step",
             units="us", kind="measured")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--arch", default="qwen15_05b")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--dry-run", action="store_true",
                    help="lower (not time) the wire; assert the byte model")
    ap.add_argument("--overlap", action="store_true",
                    help="also lower the streaming schedule and assert its "
                         "reduce-scatters interleave with backward GEMMs")
    args = ap.parse_args()

    # multi-device CPU mesh must be requested before jax initializes
    flag = "--xla_force_host_platform_device_count"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" {flag}={args.devices}")

    run(devices=args.devices, arch=args.arch, steps=args.steps,
        dry_run=args.dry_run, overlap=args.overlap)


if __name__ == "__main__":
    main()
