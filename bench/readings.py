"""Readings that set a cell's limits: the compared numbers of the program on
many seeds, of the control (the reference in the next precision down) and
of planted faults, at the cell's own size, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 10] \
        [--out readings.jsonl]

One JSON line per reading: {"workload", "seed", "what": "program" |
"control" | "<fault>", "numbers": {...}}.  Training: the program's first
three steps (and the "half_batch" fault's) against the reference's, and the
int4 control's against the reference's.  Serving: a short window at the
cell's rate, then the served tokens' gaps, and on the same tokens the gaps
of the int4 control and (``--fault-seeds``) of the reference with only its
routed experts broken: "int4_experts" and "no_experts".  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def train_readings(cell, seeds, control_seeds, fault_seeds, emit):
    import jax
    from bench import check, gen, train_cell, weights

    b = train_cell.build(cell)
    for seed in sorted(set(seeds) | set(fault_seeds)):
        key = weights.seed_key(seed)
        rows = gen.PackedRows(cell.mix, cell.cfg["vocab_size"], seed)
        runs = ([("program", None)] if seed in seeds else []) + \
            ([("half_batch", "half_batch")] if seed in fault_seeds else [])
        progs = {}
        for what, fault in runs:
            with jax.set_mesh(b.mesh):
                st = b.init(key)
                st, progs[what] = train_cell.program_first_steps(
                    b, st, rows, key, fault)
            del st
            gc.collect()
        ref = train_cell.reference_readings(cell, seed)
        for what, p in progs.items():
            emit(seed, what, check.train_numbers(p, ref),
                 {"losses": p["losses"], "ref_losses": ref["losses"],
                  "worst": check.worst_leaves(p, ref, 4),
                  "grad": p["grad"], "ref_grad": ref["grad"],
                  "change": p["change"], "ref_change": ref["change"]})
        if seed in control_seeds:
            c = train_cell.reference_readings(cell, seed, quant="int4")
            emit(seed, "control", check.train_numbers(c, ref),
                 {"losses": c["losses"], "worst": check.worst_leaves(c, ref, 4)})
        gc.collect()


def serve_readings(cell, seeds, control_seeds, fault_seeds, seconds, emit):
    import numpy as np
    from bench import gen, serve_cell

    for seed in sorted(set(seeds) | set(control_seeds) | set(fault_seeds)):
        engine = serve_cell.build_engine(cell, seed)
        serve_cell.warm(engine, cell.mix, cell.cfg["vocab_size"])
        reqs = gen.requests(cell.mix, cell.cell["rate"], seconds,
                            cell.cfg["vocab_size"], seed)
        loop = serve_cell.open_loop(engine, reqs, seconds)
        engine = None
        gc.collect()
        res = loop["results"]
        picked = serve_cell.sample(reqs, loop, seed)
        items = [(r.prompt, res[r.index]["tokens"]) for r in picked]
        quants = (["int4"] if seed in control_seeds else []) + \
            (["int4_experts", "no_experts"] if seed in fault_seeds else [])
        gaps, others = serve_cell.reference_gaps(cell, seed, items, quants)
        n = sum(len(g) for g in gaps)
        if seed in seeds:
            emit(seed, "program", {"gap": float(max(np.max(g) for g in gaps))},
                 {"tokens": n, "finished": len(res), "due": len(reqs),
                  "gap_p50": float(np.median(np.concatenate(gaps)))})
        for q, g in others.items():
            emit(seed, "control" if q == "int4" else q,
                 {"gap": float(max(np.max(x) for x in g))},
                 {"tokens": n, "gap_p50": float(np.median(np.concatenate(g)))})


def probe(cell, path):
    """Trace two training steps and write what the trace holds."""
    import tempfile
    import jax
    from bench import gen, tracefold, train_cell, weights

    b = train_cell.build(cell)
    rows = gen.PackedRows(cell.mix, cell.cfg["vocab_size"], 5)
    d = tempfile.mkdtemp()
    with jax.set_mesh(b.mesh):
        st = b.init(weights.seed_key(5))
        st, _ = train_cell.one_step(b, st, train_cell.feed(b, rows, 0, None))
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation(tracefold.WINDOW_SPAN):
            for i in (1, 2):
                with jax.profiler.TraceAnnotation("bench/step"):
                    st, _ = train_cell.one_step(
                        b, st, train_cell.feed(b, rows, i, None))
        jax.profiler.stop_trace()
    del st
    f = tracefold.find_xplane(d)
    text = tracefold.describe(f, 12)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as fh:
        fh.write(text)
    ops, spans = tracefold.read_xplane(f)
    red = tracefold.reduce(ops, spans)
    scopes = sorted({o.scope for o in red.ops})
    text += (f"\nREDUCED window {red.window_s} busy {red.busy_s} "
             f"attn {red.scope_s('stage/attn')} expert "
             f"{red.scope_s('stage/expert')}\n{json.dumps(red.breakdown)}\n"
             f"SCOPES {len(scopes)}\n" + "\n".join(scopes[:400]))
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as fh:
        fh.write(text)
    print(f"probe: window {red.window_s:.4f}s busy {red.busy_s:.4f}s",
          flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--probe", default=None, metavar="FILE",
                    help="write the layout of a traced training step to FILE")
    args = ap.parse_args(argv)

    import jax
    from bench import harness
    from repro.launch.compile_cache import enable_compile_cache

    harness.device_info(1, require_chip=True)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.listen_compiles()
    cell = harness.Cell.resolve(args.workload)
    out = open(args.out, "a") if args.out else None
    t0 = time.perf_counter()

    def emit(seed, what, numbers, detail):
        line = {"workload": args.workload, "seed": seed, "what": what,
                "numbers": numbers, "t": round(time.perf_counter() - t0, 1)}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(dict(line, detail=detail)) + "\n")
            out.flush()

    if args.probe:
        probe(cell, args.probe)
    if cell.mix["kind"] == "train":
        train_readings(cell, args.seeds, args.control_seeds, args.fault_seeds,
                       emit)
    else:
        serve_readings(cell, args.seeds, args.control_seeds, args.fault_seeds,
                       args.seconds, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
