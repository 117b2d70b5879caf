"""Sequential, resumable dry-run sweep over every (arch x shape x mesh) cell.

Appends one JSON record per cell to the output file as it goes (crash-safe);
already-present cells are skipped, so the sweep can be re-launched after
fixes and only failed/missing cells re-run.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# a CPU lowering tool: pinned to the CPU backend, so on a machine with a TPU
# it neither takes the chip nor fails for want of 512 of them
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse   # noqa: E402
import gc         # noqa: E402
import json       # noqa: E402
import signal     # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402


class CellTimeout(Exception):
    pass


def _alarm(signum, frame):
    raise CellTimeout()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dryrun/sweep.json")
    ap.add_argument("--timeout", type=int, default=900)
    ap.add_argument("--recipe", default="fp8_flow")
    ap.add_argument("--meshes", default="single,multi")
    ap.add_argument("--only-arch", default=None)
    args = ap.parse_args()

    from repro.launch.dryrun import all_cells, run_cell

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            for rec in json.load(f):
                done[(rec["arch"], rec["shape"], rec["multi_pod"],
                      rec.get("recipe", "fp8_flow"))] = rec

    meshes = []
    if "single" in args.meshes:
        meshes.append(False)
    if "multi" in args.meshes:
        meshes.append(True)

    records = list(done.values())
    signal.signal(signal.SIGALRM, _alarm)
    cells = [c for c in all_cells()
             if args.only_arch is None or c[0] == args.only_arch]
    todo = [(a, s, mp) for a, s in cells for mp in meshes
            if (a, s, mp, args.recipe) not in done
            or not done[(a, s, mp, args.recipe)].get("ok")]
    print(f"[sweep] {len(todo)} cells to run "
          f"({len(done)} cached in {args.out})", flush=True)

    for i, (arch, shape, mp) in enumerate(todo):
        key = (arch, shape, mp, args.recipe)
        signal.alarm(args.timeout)
        try:
            rec = run_cell(arch, shape, multi_pod=mp,
                           recipe_name=args.recipe)
        except CellTimeout:
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "recipe": args.recipe, "ok": False,
                   "error": f"timeout>{args.timeout}s"}
            print(f"[sweep] TIMEOUT {arch} x {shape} mp={mp}", flush=True)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                   "recipe": args.recipe, "ok": False,
                   "error": f"{type(e).__name__}: {str(e)[:500]}"}
        finally:
            signal.alarm(0)
        records = [r for r in records
                   if (r["arch"], r["shape"], r["multi_pod"],
                       r.get("recipe", "fp8_flow")) != key]
        records.append(rec)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        jax.clear_caches()
        gc.collect()
        print(f"[sweep] {i + 1}/{len(todo)} done", flush=True)

    n_ok = sum(1 for r in records if r.get("ok"))
    print(f"[sweep] finished: {n_ok}/{len(records)} ok", flush=True)


if __name__ == "__main__":
    main()
