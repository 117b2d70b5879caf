"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek_v2_lite \
      --recipe fp8_flow --steps 100 [--reduced] [--ckpt-dir DIR] \
      [--elastic] [--dist-wire fp8] [--dist-schedule stream] \
      [--remat-policy fp8_resident] [--grad-accum N]

Without --reduced the mesh spans the devices present: every chip on the
'model' axis (expert parallel), or on the 'data' axis when the DP wire is
on.  The train state is created already placed by launch/sharding.py's
param_specs / opt_state_specs, and the unguarded step donates it.  On a
fleet the process runs once per host under `jax.distributed.initialize()`.
--reduced cuts every width to the CPU smoke size (configs/base.py).

--dist-wire {off,fp8,bf16,f32} selects the explicit DP communication plan
(repro.dist.DistPlan): quantized ZeRO-1 gradient reduce-scatter + FP8-split
optimizer state.  It replaces the old implicit pjit-psum reduction (and the
never-wired --compress-pod-grads flag).  The wire needs a DP-only mesh, so
with --reduced the test mesh spans every visible device on the data axis.

--dist-schedule {posthoc,stream} picks WHEN the wire runs: 'posthoc'
reduces every bucket after the full backward; 'stream' aligns buckets to
layer boundaries and issues each bucket's quantize + reduce-scatter from
inside the staged backward the moment its layer's grads exist, hiding the
DP wire behind the remaining backward compute.  --grad-accum N streams
too: microbatch grads accumulate locally and each bucket goes on the wire
once, from the last microbatch's backward.  When the configuration cannot
stream (encoder-decoder arch, buckets that do not align to layer
boundaries) the launcher warns and falls back to 'posthoc' instead of
miscompiling.

--remat-policy selects the activation-residency plan
(train/memory.py MemoryPlan): 'fp8_resident' keeps only the QTensor stage
outputs across the forward/backward boundary (the paper's memory claim),
'pair' checkpoints two-layer blocks (compile-time lever at depth).

--guard arms the numerics guardrails (train/guards.py): train_step emits an
in-step anomaly bitmask (nonfinite loss/grads, grad-norm spikes vs a
carried EMA, FP8 saturation/underflow-flush fractions, wire-guard trips)
and the loop runs the skip -> rollback -> bf16-demote recovery ladder.
"""
import argparse
import dataclasses
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.core.recipes import Recipe, get_recipe
from repro.data.pipeline import DataConfig, make_batch
from repro.dist import DistPlan
from repro.dist.grad_comm import wire_grad_bytes
from repro.launch import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_test_mesh
from repro.optim import adamw
from repro.runtime.fault_tolerance import ElasticTrainer
from repro.train.guards import GuardPlan, GuardPolicy
from repro.train.loop import run as run_loop
from repro.train.train_step import init_train_state, make_train_step


def reduced_mesh(dist):
    """The --reduced mesh: (1, 1), or DP-only over the visible devices when
    the DP wire is on (its size must divide DistPlan.shard_multiple for
    equal ZeRO shards)."""
    if dist is None:
        return make_test_mesh()
    ndev = max(d for d in range(1, jax.device_count() + 1)
               if dist.shard_multiple % d == 0
               and jax.device_count() % d == 0)
    if ndev < jax.device_count():
        print(f"[train] WARNING: DP size clamped to {ndev} of "
              f"{jax.device_count()} devices (must divide "
              f"DistPlan.shard_multiple={dist.shard_multiple}); "
              f"the rest sit idle")
    return make_test_mesh((ndev, 1))


def state_shardings(cfg: ArchConfig, mesh, state_shapes, dist):
    """NamedShardings for the train state: params by param_specs, optimizer
    state ZeRO-1 (opt_state_specs, or dist_state_specs for the DP wire's
    flat buckets), the guard's EMA replicated."""
    params_sh = sharding.tree_specs(cfg, mesh, state_shapes["params"])
    if dist is not None:
        opt_sh = sharding.dist_state_specs(mesh, state_shapes["opt"],
                                           dist.axis)
    else:
        opt_sh = sharding.opt_state_specs(cfg, mesh, params_sh,
                                          state_shapes["opt"])
    out = {"params": params_sh, "opt": opt_sh}
    if "guard" in state_shapes:
        out["guard"] = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                    state_shapes["guard"])
    return out


def run(cfg: ArchConfig, *, recipe: Recipe, steps: int, seq_len: int,
        global_batch: int, lr: float = 1e-3, mesh=None, seed: int = 0,
        dist=None, grad_accum: int = 1, guard=None, guard_policy=None,
        ckpt_dir=None, elastic: bool = False, telemetry=None,
        log_fn=print):
    """Train ``cfg`` for ``steps`` steps; returns (state, history, info).

    mesh=None builds one over the devices present (make_device_mesh).  The
    state is initialised straight into its shardings; the step is compiled
    ahead of the loop, so ``info['compile_s']`` is compile time apart from
    the steps in ``history``.  Without a guard the step donates the state:
    old and new state never coexist on the device.  The guarded loop keeps
    the previous state alive for skip-step, so a guarded run holds two
    copies of the state at the step boundary."""
    if mesh is None:
        mesh = make_device_mesh(model=1 if dist is not None else None)
    plan = sharding.make_plan(cfg, mesh)
    opt = adamw.AdamWConfig(lr=lr)
    log_fn(f"[train] {cfg.name} ({cfg.n_params()/1e9:.2f}B params) "
           f"recipe={recipe.name} pallas={recipe.use_pallas} "
           f"mesh={dict(mesh.shape)}")

    def init(key):
        return init_train_state(cfg, opt, key, dist=dist, guard=guard)

    key = jax.random.key(seed)
    state_sh = state_shardings(cfg, mesh, jax.eval_shape(init, key), dist)
    state = jax.jit(init, out_shardings=state_sh)(key)

    if dist is not None:
        from repro.dist import build_layout, streaming_fallback_reason
        layout = build_layout(state["params"], dist)
        n_dp = mesh.shape[dist.axis]
        gbytes = wire_grad_bytes(cfg.n_params(), n_dp, dist.wire)
        log_fn(f"[train] dist wire={dist.wire} schedule={dist.schedule} "
               f"zero1 over '{dist.axis}' x{n_dp}: ~{gbytes/2**20:.0f} "
               f"MiB grad bytes/step/device (bf16 all-reduce: "
               f"{wire_grad_bytes(cfg.n_params(), n_dp, 'bf16', 'none')/2**20:.0f}"
               f" MiB)")
        if telemetry is not None:
            # static wire accounting: one layout event + a modelled
            # bytes/step counter the loop increments every step
            telemetry.record("wire_layout", wire=dist.wire,
                             schedule=dist.schedule, n_dp=n_dp,
                             n_buckets=len(layout.buckets),
                             n_sensitive=len(layout.sensitive),
                             n_leaves=layout.n_leaves,
                             fp8_elems=layout.fp8_elems,
                             wire_rows=layout.wire_rows,
                             grad_bytes_per_step=gbytes)
            telemetry.per_step_counters["wire_grad_bytes_total"] = gbytes
            telemetry.per_step_counters["wire_buckets_total"] = \
                len(layout.buckets)
        if dist.schedule == "stream":
            # fast clear fallback: if the layout's buckets cannot align to
            # layer boundaries (or the config cannot stream), warn and run
            # post-hoc — the layered layout is kept, so the ZeRO-1 state
            # stays valid
            reason = streaming_fallback_reason(cfg, layout,
                                               grad_accum=grad_accum)
            if reason:
                log_fn(f"[train] WARNING: streaming wire unavailable "
                       f"({reason}); falling back to the post-hoc schedule")
                dist = dataclasses.replace(dist, schedule="posthoc")

    data = DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                      global_batch=global_batch, seed=seed)
    with jax.set_mesh(mesh):        # the loop makes its batches under it too
        batch0 = make_batch(data, 0)
    if grad_accum > 1:
        batch0 = jax.tree.map(
            lambda a: a.reshape(grad_accum, a.shape[0] // grad_accum,
                                *a.shape[1:]), batch0)
    batch_sh = sharding.batch_specs(mesh, batch0, plan.dp_axes)

    def build(rcp):
        fn = make_train_step(cfg, rcp, plan, opt, dist=dist,
                             grad_accum=grad_accum, total_steps=steps,
                             warmup_steps=max(steps // 10, 1), guard=guard)
        return jax.jit(fn, in_shardings=(state_sh, batch_sh),
                       donate_argnums=() if guard is not None else (0,))

    step = build(recipe)
    fallback = None
    if guard is not None and recipe.name != "bf16":
        # graceful degradation target: same arch/plan/opt under the bf16
        # recipe (no quantize sites), still guard-instrumented so the
        # ladder keeps observing while demoted
        fallback = build(get_recipe("bf16"))

    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        step.lower(state, batch0).compile()
    compile_s = time.perf_counter() - t0
    log_fn(f"[train] compiled the step in {compile_s:.1f}s")

    restore_sh = state_sh if (dist is not None and ckpt_dir is not None) \
        else None
    trainer = ElasticTrainer(n_data_shards=mesh.shape["data"]) \
        if elastic else None
    with jax.set_mesh(mesh):
        state, hist = run_loop(step, state, data, n_steps=steps,
                               grad_accum=grad_accum, ckpt_dir=ckpt_dir,
                               elastic=trainer, restore_shardings=restore_sh,
                               guard_policy=guard_policy,
                               fallback_step=fallback, telemetry=telemetry,
                               log_fn=log_fn)
    return state, hist, {"compile_s": compile_s, "mesh": mesh}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek_v2_lite")
    ap.add_argument("--recipe", default="fp8_flow")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--elastic", action="store_true")
    ap.add_argument("--dist-wire", default="off",
                    choices=["off", "fp8", "bf16", "f32"],
                    help="explicit DP gradient wire + ZeRO-1 (repro.dist)")
    ap.add_argument("--dist-schedule", default="posthoc",
                    choices=["posthoc", "stream"],
                    help="reduce buckets after the backward (posthoc) or "
                         "stream them out of the staged backward in reverse "
                         "layer order (stream)")
    ap.add_argument("--remat-policy", default=None,
                    choices=["none", "full", "fp8_resident", "pair"],
                    help="activation-residency plan (train/memory.py "
                         "MemoryPlan): none = save everything, full = bf16 "
                         "stage checkpointing, fp8_resident = keep only the "
                         "QTensor stage outputs across fwd/bwd, pair = "
                         "checkpoint-of-pairs (compile-time lever)")
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches per step; with --dist-schedule "
                         "stream the wire runs once, from inside the last "
                         "microbatch's backward")
    ap.add_argument("--guard", action="store_true",
                    help="arm the numerics guardrails (train/guards.py): "
                         "in-step anomaly bitmask + skip/rollback/demote "
                         "recovery ladder with a bf16 fallback step")
    ap.add_argument("--guard-spike-factor", type=float, default=4.0,
                    help="grad-norm spike threshold as a multiple of the "
                         "carried EMA")
    ap.add_argument("--guard-rollback-after", type=int, default=3,
                    help="consecutive anomalous steps before restoring the "
                         "last valid checkpoint")
    ap.add_argument("--guard-demote-steps", type=int, default=8,
                    help="length of the bf16 fallback window entered after "
                         "persistent anomalies")
    ap.add_argument("--obs-jsonl", default=None, metavar="PATH",
                    help="write structured telemetry (typed loop events, "
                         "per-step samples with per-site FP8 sat/flush, "
                         "cast-ledger snapshots) as JSONL; feed the file to "
                         "`python -m repro.obs.report`")
    ap.add_argument("--obs-prom", default=None, metavar="PATH",
                    help="write a Prometheus text-exposition snapshot of "
                         "the metrics registry at exit")
    args = ap.parse_args()

    enable_compile_cache()
    from repro.obs.sink import JsonlSink, Telemetry
    tel = None
    if args.obs_jsonl is not None or args.obs_prom is not None:
        sinks = (JsonlSink(args.obs_jsonl),) if args.obs_jsonl else ()
        tel = Telemetry(sinks=sinks)

    dist = DistPlan(wire=args.dist_wire, schedule=args.dist_schedule) \
        if args.dist_wire != "off" else None
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.remat_policy is not None:
        cfg = dataclasses.replace(cfg, remat_policy=args.remat_policy)
    guard = policy = None
    if args.guard:
        guard = GuardPlan(spike_factor=args.guard_spike_factor)
        policy = GuardPolicy(rollback_after=args.guard_rollback_after,
                             demote_steps=args.guard_demote_steps)
        print(f"[train] guardrails armed: spike_factor="
              f"{args.guard_spike_factor} rollback_after="
              f"{args.guard_rollback_after} demote_steps="
              f"{args.guard_demote_steps}")
    state, hist, _ = run(
        cfg, recipe=get_recipe(args.recipe), steps=args.steps,
        seq_len=args.seq_len, global_batch=args.global_batch, lr=args.lr,
        mesh=reduced_mesh(dist) if args.reduced else None, dist=dist,
        grad_accum=args.grad_accum, guard=guard, guard_policy=policy,
        ckpt_dir=args.ckpt_dir, elastic=args.elastic, telemetry=tel)
    print(f"[train] done: loss {hist[0]['loss']:.4f} -> "
          f"{hist[-1]['loss']:.4f}")
    dev_ms = [h["device_ms"] for h in hist]
    fetch_ms = [h["fetch_ms"] for h in hist]
    print(f"[train] timing: device {sum(dev_ms)/len(dev_ms):.1f}ms/step, "
          f"host fetch {sum(fetch_ms)/len(fetch_ms):.1f}ms/step "
          f"({len(hist)} steps)")
    if args.obs_prom is not None:
        tel.write_prometheus(args.obs_prom)
        print(f"[train] wrote metrics snapshot to {args.obs_prom}")
    if args.obs_jsonl is not None:
        tel.emit_registry()
        tel.close()
        print(f"[train] wrote telemetry to {args.obs_jsonl} "
              f"(report: python -m repro.obs.report {args.obs_jsonl})")


if __name__ == "__main__":
    main()
