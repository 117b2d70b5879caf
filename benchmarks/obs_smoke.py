"""Observability smoke: exercise the whole repro.obs surface end to end.

Runs a short guarded training loop AND a continuous-batching serve trace,
both writing structured telemetry through JsonlSink, then:

  * renders the combined stream with `repro.obs.report` (the CLI reporter
    must understand every record kind the stack emits);
  * asserts the ZERO-HOST-SYNC structural gate on the instrumented train
    step — jaxpr + compiled HLO contain no callback / infeed / outfeed /
    send / recv ops, i.e. all device-side telemetry rides the loop's one
    existing per-step metrics fetch;
  * asserts the record inventory: one step record per train step with the
    per-site sat/flush matrix, a cast-ledger snapshot per traced program,
    a serve_tick stream, one request_done (with TTFT/TBT) per request, and
    a serve_summary matching the engine's aggregate counters.

  PYTHONPATH=src python benchmarks/obs_smoke.py                  # CI job
  PYTHONPATH=src python benchmarks/obs_smoke.py --out /tmp/obs   # keep files
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import tempfile

_HOST_TRANSFER_TOKENS = ("callback", "infeed", "outfeed", "send", "recv")


def _host_transfer_counts(text: str):
    low = text.lower()
    return {t: len(re.findall(rf"\b{t}", low)) for t in _HOST_TRANSFER_TOKENS}


def run(train_steps: int = 5, requests: int = 20, out_dir=None):
    import jax
    import numpy as np

    try:
        import benchmarks.common  # noqa: F401  (path bootstrap only)
    except ModuleNotFoundError:      # invoked as `python benchmarks/...py`
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    from repro.launch.mesh import make_mesh
    from repro.configs import get_arch
    from repro.core import quant as quant_stats
    from repro.core.recipes import get_recipe
    from repro.data.pipeline import DataConfig
    from repro.models.lm import ParallelPlan, init_params
    from repro.obs.report import by_kind, load_records, render
    from repro.obs.sink import JsonlSink, Telemetry
    from repro.optim.adamw import AdamWConfig
    from repro.serve.engine import ServeConfig, ServeEngine
    from repro.serve.scheduler import Request
    from repro.train.guards import GuardPlan, GuardPolicy
    from repro.train.loop import run as run_loop
    from repro.train.train_step import init_train_state, make_train_step

    if out_dir is None:
        out_dir = tempfile.mkdtemp(prefix="obs_smoke_")
    os.makedirs(out_dir, exist_ok=True)
    train_path = os.path.join(out_dir, "train.jsonl")
    serve_path = os.path.join(out_dir, "serve.jsonl")

    # -- guarded train loop with telemetry ---------------------------------
    cfg = get_arch("qwen15_05b").reduced()
    mesh = make_mesh((1, 1), ("data", "model"))
    plan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    opt = AdamWConfig(lr=3e-3)
    recipe = get_recipe("fp8_flow")
    guard = GuardPlan()
    data = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=2)
    raw = make_train_step(cfg, recipe, plan, opt, total_steps=100,
                          warmup_steps=5, guard=guard)
    state = init_train_state(cfg, opt, jax.random.key(0), guard=guard)

    tel = Telemetry(sinks=(JsonlSink(train_path),))
    with mesh:
        run_loop(jax.jit(raw), state, data, n_steps=train_steps,
                 log_every=1, guard_policy=GuardPolicy(), telemetry=tel)
    tel.emit_registry()
    tel.close()

    # -- zero-host-sync structural gate on the instrumented step -----------
    from repro.data.pipeline import make_batch
    batch = make_batch(data, 0)
    with mesh:
        jaxpr = str(jax.make_jaxpr(raw)(state, batch))
        hlo = jax.jit(raw).lower(state, batch).compile().as_text()
    for name, text in (("jaxpr", jaxpr), ("hlo", hlo)):
        counts = _host_transfer_counts(text)
        assert not any(counts.values()), (
            f"instrumented {name} contains host-transfer ops {counts} — "
            f"telemetry must ride the existing metrics fetch")
    assert "stage/" in hlo, "stage scopes missing from compiled HLO"
    print("[obs_smoke] zero-host-sync gate: jaxpr + HLO clean, "
          "stage scopes present")

    # -- serve trace with telemetry ----------------------------------------
    scfg = get_arch("qwen3_moe_235b").reduced()
    splan = ParallelPlan(mesh=mesh, dp_axes=("data",))
    srecipe = get_recipe("fp8_flow")
    params = init_params(scfg, jax.random.key(0))
    ecfg = ServeConfig(max_batch=4, page_size=8, n_pages=64,
                       max_pages_per_req=6, token_budget=256,
                       prefill_buckets=(16,), fp8_kv=True, w8_weights=True)
    stel = Telemetry(sinks=(JsonlSink(serve_path),))
    eng = ServeEngine(scfg, srecipe, splan, params, ecfg, telemetry=stel)
    r = np.random.default_rng(0)
    reqs = [Request(prompt=list(r.integers(1, scfg.vocab,
                                           int(r.integers(4, 12)))),
                    max_new_tokens=4)
            for _ in range(requests)]
    results = eng.run(reqs, realtime=False)
    stats = results.stats
    stel.emit_registry()
    stel.close()
    assert len(results) == requests, "requests lost"
    assert stats["finished"] == requests

    # -- record inventory ---------------------------------------------------
    recs = load_records([train_path, serve_path])
    kinds = by_kind(recs)
    steps = kinds.get("step", [])
    assert len(steps) == train_steps, (len(steps), train_steps)
    for s in steps:
        assert {"device_ms", "fetch_ms", "loss"} <= set(s)
        assert set(s.get("quant_sites", {})) == set(quant_stats.STAT_SITES)
    assert len(kinds.get("cast_ledger", [])) >= 1
    assert len(kinds.get("request_done", [])) == requests
    assert all("ttft_ms" in d for d in kinds["request_done"])
    assert len(kinds.get("serve_tick", [])) == stats["ticks"]
    summ = kinds.get("serve_summary", [])
    assert len(summ) == 1 and summ[0]["finished"] == stats["finished"]
    print(f"[obs_smoke] record inventory: {len(steps)} steps, "
          f"{len(kinds['cast_ledger'])} cast ledgers, "
          f"{stats['ticks']} serve ticks, {requests} request_done")

    # -- the reporter renders the full stream -------------------------------
    n = render(recs)
    assert n == len(recs)
    print(f"obs_smoke: OK — {n} records rendered from "
          f"{os.path.basename(train_path)} + {os.path.basename(serve_path)}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--train-steps", type=int, default=5)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--out", default=None,
                    help="directory for the JSONL files (default: tmpdir)")
    args = ap.parse_args()
    run(train_steps=args.train_steps, requests=args.requests,
        out_dir=args.out)


if __name__ == "__main__":
    main()
