"""Find the highest request rate a serving cell's engine sustains, and write
0.8 of it into the cell's file as its fixed rate.

    python3 bench/sweep.py --workload <cell> --rates 2,3,4,6 [--seconds 20] \
        [--seed 1] [--write]

One process builds the engine once and offers each rate, lowest first, for
``--seconds`` of open-loop traffic from the cell's mix; after each window
the backlog is drained untimed.  A rate is sustained when the queue does
not grow through the window: at the close at most ``max_batch / 4``
requests wait unadmitted, and the 90th-percentile TTFT of the window's last
third of requests is at most twice that of its first third plus 0.25 s.
With ``--write`` the cell's ``rate`` becomes 0.8 x the highest sustained
rate (``knee`` and the sweep's readings are kept beside it).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def sustained(reqs, loop, engine) -> dict:
    import numpy as np
    from bench import serve_cell

    ttft, _ = serve_cell.latencies(reqs, loop)
    n = len(ttft)
    third = max(n // 3, 1)
    first = float(np.percentile(ttft[:third], 90))
    last = float(np.percentile(ttft[-third:], 90))
    waiting = len(engine.sched.waiting)
    ok = waiting <= max(engine.ecfg.max_batch // 4, 1) \
        and last <= 2 * first + 0.25
    return {"due": n, "finished": len(loop["results"]), "waiting": waiting,
            "ttft_p90_first_s": first, "ttft_p90_last_s": last,
            "ttft_p90_s": float(np.percentile(ttft, 90)), "sustained": ok}


def drain(engine):
    results = {}
    while engine.sched.waiting or engine.sched.active:
        engine.tick(0.0, results)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: sorted(float(x) for x in s.split(",")))
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from bench import gen, harness, serve_cell
    from repro.launch.compile_cache import enable_compile_cache

    harness.device_info(1, require_chip=True)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.Cell.resolve(args.workload)
    engine = serve_cell.build_engine(cell, args.seed)
    serve_cell.warm(engine, cell.mix, cell.cfg["vocab_size"])
    readings, knee = [], None
    for rate in args.rates:
        reqs = gen.requests(cell.mix, rate, args.seconds,
                            cell.cfg["vocab_size"], args.seed)
        loop = serve_cell.open_loop(engine, reqs, args.seconds)
        r = dict(sustained(reqs, loop, engine), rate=rate)
        readings.append(r)
        print(json.dumps(r), flush=True)
        drain(engine)
        if r["sustained"]:
            knee = rate
        else:
            break
    print(json.dumps({"workload": args.workload, "knee": knee}), flush=True)
    if args.write and knee:
        path = cell.root / "bench" / "cells" / f"{args.workload}.json"
        data = harness.load_json(path)
        data.update(rate=round(0.8 * knee, 3), knee=knee, sweep=readings)
        path.write_text(json.dumps(data, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
