"""Guarded vs unguarded train-step A/B: the guardrails must be (near) free.

The whole design constraint of train/guards.py is that detection rides the
metrics fetch the loop ALREADY does every step — anomaly folding happens
in-jit on replica-uniform scalars, FP8 site stats are a 2-float max-merge
threaded through the existing carries, and the wire guard is a lax.cond on
a pmax'd predicate.  Nothing may add a host round-trip.

This bench builds the SAME tiny model twice (guard=None vs GuardPlan) and
checks exactly that:

  structural (the CI gate, --dry-run):
    * the guarded jaxpr + compiled HLO contain ZERO additional host
      transfer ops (callbacks / infeed / outfeed / host send-recv) over
      the unguarded build — detection is computed on device and fetched
      with the loss;
    * the unguarded jaxpr is free of guard artifacts (no uint32 anomaly
      fold, no quantize-site stat outputs) — guards off costs nothing.

  measured (full run):
    * median step wall-clock for both builds -> overhead %.

  PYTHONPATH=src python benchmarks/guard_overhead_ab.py --dry-run   # CI
  PYTHONPATH=src python benchmarks/guard_overhead_ab.py --steps 5
"""
from __future__ import annotations

import argparse
import os
import re
import sys

# ops that move data between host and device: any of these appearing in the
# guarded build but not the unguarded one means detection broke the
# zero-extra-syncs contract
_HOST_TRANSFER_TOKENS = ("callback", "infeed", "outfeed", "send", "recv")


def _host_transfer_counts(text: str):
    low = text.lower()
    return {t: len(re.findall(rf"\b{t}", low)) for t in _HOST_TRANSFER_TOKENS}


def run(arch: str = "qwen15_05b", steps: int = 5, dry_run: bool = False):
    import jax

    try:
        from benchmarks.common import emit, time_fn
    except ModuleNotFoundError:      # invoked as `python benchmarks/...py`
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from benchmarks.common import emit, time_fn
    from repro.launch.mesh import make_mesh
    from repro.configs import get_arch
    from repro.core.recipes import get_recipe
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models.lm import ParallelPlan
    from repro.optim.adamw import AdamWConfig
    from repro.train.guards import GuardPlan
    from repro.train.train_step import init_train_state, make_train_step

    cfg = get_arch(arch).reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=3e-3)
    recipe = get_recipe("fp8_flow")
    data = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=4)

    builds = {}
    for name, guard in [("unguarded", None), ("guarded", GuardPlan())]:
        raw = make_train_step(cfg, recipe, plan, opt,
                              total_steps=1000, warmup_steps=5, guard=guard)
        state = init_train_state(cfg, opt, jax.random.key(0), guard=guard)
        batch = make_batch(data, 0)
        with mesh:
            jaxpr = str(jax.make_jaxpr(raw)(state, batch))
            lowered = jax.jit(raw).lower(state, batch)
            hlo = lowered.compile().as_text()
        builds[name] = dict(raw=raw, state=state, batch=batch,
                            jaxpr=jaxpr, hlo=hlo)

    # -- structural gate ----------------------------------------------------
    for text_key in ("jaxpr", "hlo"):
        cu = _host_transfer_counts(builds["unguarded"][text_key])
        cg = _host_transfer_counts(builds["guarded"][text_key])
        extra = {t: cg[t] - cu[t] for t in cu if cg[t] > cu[t]}
        assert not extra, (
            f"guarded {text_key} adds host transfer ops {extra} — the "
            f"guardrails must not introduce device->host syncs")
        print(f"[guard_ab] {text_key}: host-transfer ops guarded == "
              f"unguarded ({ {t: cg[t] for t in cg} })")

    ju = builds["unguarded"]["jaxpr"]
    assert "guard" not in ju and "u32" not in ju.split("let")[0], \
        "unguarded jaxpr carries guard artifacts"
    print("[guard_ab] unguarded jaxpr is guard-free")

    eq_u = ju.count("\n")
    eq_g = builds["guarded"]["jaxpr"].count("\n")
    print(f"[guard_ab] jaxpr lines: unguarded={eq_u} guarded={eq_g} "
          f"(+{eq_g - eq_u} for detection)")

    if dry_run:
        print("guard_overhead_ab: DRY-RUN OK — zero extra host transfers")
        return

    # -- measured overhead --------------------------------------------------
    times = {}
    with mesh:
        for name, b in builds.items():
            step = jax.jit(b["raw"])
            st = b["state"]

            def one(st=st, step=step, batch=b["batch"]):
                new_st, metrics = step(st, batch)
                return metrics["loss"]

            times[name] = time_fn(one, iters=steps, warmup=2)
            emit(f"train_step_{name}", times[name], f"arch={arch}",
                 units="us", kind="measured")
    ovh = (times["guarded"] / times["unguarded"] - 1.0) * 100.0
    emit("guard_overhead", times["guarded"] - times["unguarded"],
         f"overhead_pct={ovh:.2f}",
         units="us", kind="measured")
    print(f"[guard_ab] guard overhead: {ovh:+.2f}% wall-clock")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen15_05b")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()
    run(arch=args.arch, steps=args.steps, dry_run=args.dry_run)


if __name__ == "__main__":
    main()
