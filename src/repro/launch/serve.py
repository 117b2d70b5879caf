"""Serving launcher: continuous-batching engine over paged FP8 KV and
W8-resident weights.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_moe_235b \
      --reduced --requests 16 [--bf16-kv] [--no-w8]

Drives a synthetic trace through serve/engine.py: FCFS admission against a
token budget, interleaved bucketed prefill + masked full-batch decode in one
jitted step, youngest-first eviction under page pressure.  The old
fixed-batch shared-position loop lives on in serve/serve_step.py for the
dry-run shape cells.

Without --reduced the mesh spans the devices present and the parameters
are placed by launch/sharding.py's specs; --reduced cuts every width to the
CPU smoke size (configs/base.py).
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.core.recipes import Recipe, get_recipe
from repro.launch import sharding
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_device_mesh, make_test_mesh
from repro.models.lm import init_params
from repro.serve.engine import ServeConfig, ServeEngine
from repro.serve.scheduler import Request


def run(cfg: ArchConfig, *, recipe: Recipe, ecfg: ServeConfig, requests,
        mesh=None, seed: int = 0, disagg=None, prefill_replicas: int = 1,
        decode_replicas: int = 1, telemetry=None, log_fn=print):
    """Serve ``requests`` to completion; returns (results, engine, params).

    mesh=None builds one over the devices present (make_device_mesh).  The
    parameters are initialised from ``seed`` straight into their
    launch/sharding.py shardings; ``params`` is that unquantized tree (the
    engine holds its own W8 copy when ``ecfg.w8_weights``).  ``disagg`` (a
    serve.router.DisaggConfig) runs a prefill/decode fleet instead of one
    engine; ``engine`` is then the first prefill replica."""
    from repro.obs.sink import null_telemetry
    tel = telemetry if telemetry is not None else null_telemetry()
    if mesh is None:
        mesh = make_device_mesh()
    plan = sharding.make_plan(cfg, mesh)

    def init(key):
        return init_params(cfg, key)

    key = jax.random.key(seed)
    params_sh = sharding.tree_specs(cfg, mesh, jax.eval_shape(init, key))
    params = jax.jit(init, out_shardings=params_sh)(key)
    if disagg is not None:
        from repro.serve.router import DisaggRouter
        pes = [ServeEngine(cfg, recipe, plan, params,
                           dataclasses.replace(ecfg, role="prefill"),
                           telemetry=tel)
               for _ in range(prefill_replicas)]
        des = [ServeEngine(cfg, recipe, plan, params,
                           dataclasses.replace(ecfg, role="decode"),
                           telemetry=tel)
               for _ in range(decode_replicas)]
        runner = DisaggRouter(pes, des, dcfg=disagg, telemetry=tel)
        engine = pes[0]
        log_fn(f"[serve] disaggregated fleet: {len(pes)} prefill + "
               f"{len(des)} decode replicas, transfer budget "
               f"{disagg.transfer_budget_bytes / 2**20:.2f} MiB/cycle")
    else:
        engine = ServeEngine(cfg, recipe, plan, params, ecfg, telemetry=tel)
        runner = engine
    log_fn(f"[serve] {cfg.name} recipe={recipe.name} "
           f"kv={'fp8' if ecfg.fp8_kv else 'bf16'} "
           f"w8={ecfg.w8_weights} pool={engine.kv_bytes()/2**20:.1f} MiB "
           f"mesh={dict(mesh.shape)}")
    results = runner.run(requests, realtime=False)
    return results, engine, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_moe_235b")
    ap.add_argument("--recipe", default="fp8_flow")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--n-pages", type=int, default=128)
    ap.add_argument("--max-pages", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=512)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: max prompt tokens per tick")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: shared page-aligned prompt "
                         "prefixes are quantized+prefilled once and reused "
                         "across requests (refcounted FP8 KV pages)")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation: a two-tier fleet "
                         "(prefill replicas park finished prefills; KV pages "
                         "migrate bit-for-bit to decode replicas under a "
                         "transfer-bytes budget)")
    ap.add_argument("--prefill-replicas", type=int, default=1)
    ap.add_argument("--decode-replicas", type=int, default=1)
    ap.add_argument("--transfer-budget", type=int, default=1 << 20,
                    metavar="BYTES",
                    help="KV migration wire bytes per router drain cycle")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--bf16-kv", action="store_true")
    ap.add_argument("--no-w8", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--obs-jsonl", default=None, metavar="PATH",
                    help="write structured serve telemetry (per-tick "
                         "records, request_done events with TTFT/TBT, "
                         "KV-pool occupancy) as JSONL for "
                         "`python -m repro.obs.report`")
    ap.add_argument("--obs-prom", default=None, metavar="PATH",
                    help="write a Prometheus text-exposition snapshot of "
                         "the serve metrics registry at exit")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    mesh = None
    if args.reduced:
        cfg = cfg.reduced()
        mesh = make_test_mesh()
    recipe = get_recipe(args.recipe)
    fp8 = recipe.name == "fp8_flow"
    ecfg = ServeConfig(
        max_batch=args.max_batch, page_size=args.page_size,
        n_pages=args.n_pages, max_pages_per_req=args.max_pages,
        token_budget=args.token_budget, prefill_buckets=(16, 32, 64),
        prefill_chunk=args.prefill_chunk,
        fp8_kv=fp8 and not args.bf16_kv,
        w8_weights=fp8 and not args.no_w8,
        prefix_cache=args.prefix_cache, seed=args.seed)
    from repro.obs.sink import JsonlSink, Telemetry, null_telemetry
    if args.obs_jsonl is not None or args.obs_prom is not None:
        sinks = (JsonlSink(args.obs_jsonl),) if args.obs_jsonl else ()
        tel = Telemetry(sinks=sinks)
    else:
        tel = null_telemetry()
    disagg = None
    if args.disagg:
        from repro.serve.router import DisaggConfig
        disagg = DisaggConfig(transfer_budget_bytes=args.transfer_budget)

    r = np.random.default_rng(args.seed)
    reqs = [Request(prompt=list(r.integers(1, cfg.vocab,
                                           int(r.integers(3, 17)))),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    results, engine, _ = run(
        cfg, recipe=recipe, ecfg=ecfg, requests=reqs, mesh=mesh,
        seed=args.seed, disagg=disagg,
        prefill_replicas=args.prefill_replicas,
        decode_replicas=args.decode_replicas, telemetry=tel)
    dt = time.perf_counter() - t0
    n_tok = sum(len(v["tokens"]) for v in results.values())
    print(f"[serve] {len(results)}/{args.requests} requests, {n_tok} tokens "
          f"in {dt:.2f}s ({n_tok / dt:.1f} tok/s), "
          f"max concurrent {engine.max_concurrent}")
    s = results.stats
    print(f"[serve] ticks={s['ticks']} admitted={s['admitted']} "
          f"evicted={s['evicted']} finished={s['finished']} "
          f"prefill_chunks={s['prefill_chunks']} "
          f"decode_tokens={s['decode_tokens']}")
    if args.disagg:
        d = s["disagg"]
        print(f"[serve] disagg: migrations={d['migrations']} "
              f"wire={d['kv_transfer_bytes'] / 2**20:.2f} MiB "
              f"shipped_pages={d['shipped_pages']} "
              f"deduped_pages={d['deduped_pages']} "
              f"requeued={d['requeued_evictions']} "
              f"deferrals={d['budget_deferrals']}")
    if args.prefix_cache:
        total_prompt = sum(len(q.prompt) for q in reqs)
        print(f"[serve] prefix cache: hits={s['prefix_hits']}/"
              f"{s['prefix_lookups']} hit_tokens={s['prefix_hit_tokens']}"
              f"/{total_prompt} shared_pages={s['shared_pages']} "
              f"cache_evictions={s['cache_evictions']}")
    if args.obs_prom is not None:
        tel.write_prometheus(args.obs_prom)
        print(f"[serve] wrote metrics snapshot to {args.obs_prom}")
    if args.obs_jsonl is not None:
        tel.emit_registry()
        tel.close()
        print(f"[serve] wrote telemetry to {args.obs_jsonl} "
              f"(report: python -m repro.obs.report {args.obs_jsonl})")


if __name__ == "__main__":
    main()
