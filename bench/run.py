"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``).  With ``--trace 0`` the line carries
the cell's end-to-end metrics; with ``--trace 1`` a profiler trace of part
of the window is read and the line carries the per-layer metrics, the
device's busy and window seconds and a breakdown.  Without an accelerator,
or with fewer chips than the cell needs, it exits 3 and prints no result.
The compile cache is the program's own (``JAX_COMPILATION_CACHE_DIR``, else
``.jax_cache/`` in the checkout), with every program cached however fast
it compiled.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    try:
        harness.device_info(1, require_chip=True)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 3
    harness.print_compared(out["compared"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
