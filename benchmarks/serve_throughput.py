"""Continuous-batching serving throughput: tok/s and p50/p99 latency under a
synthetic Poisson arrival trace, fp8_flow (W8-resident weights + FP8 paged
KV) vs bf16 (BF16 weights + BF16 paged KV).

  PYTHONPATH=src python benchmarks/serve_throughput.py --reduced \
      [--requests 32] [--rate 20] [--arch qwen3_moe_235b] \
      [--prefill-chunk 16] [--compare-prefill] \
      [--shared-prefix 4] [--compare-prefix-cache]

Reports, per recipe (and per prefill mode with --compare-prefill, which runs
the SAME trace chunked vs monolithic so the decode-latency / TTFT win of
bounded prefill slices is measured, not asserted):
  tok/s        — generated tokens / makespan
  p50/p99 lat  — request completion latency (arrival -> last token)
  p50/p99 ttft — time to first token (arrival -> first sampled token)
  hit rate     — prefix-cache hit tokens / total prompt tokens (cache on)
  kv bytes     — resident paged-pool footprint (FP8 pages ~halve this)

--shared-prefix K generates a MULTI-TENANT trace: K tenants, each with its
own fixed system prompt, every request = tenant prefix + unique tail — the
workload the radix prefix cache targets.  --compare-prefix-cache runs the
same trace cache-on vs cache-off so the hit-rate -> TTFT effect is measured.

--preset decode_heavy (short prompts, long generations) and
--preset mixed_interference (decode-heavy foreground + periodic long-prompt
prefills) target the time-between-tokens TAIL: every run also reports
per-token p50/p99 TBT from the engine's serve_tbt_ms histogram, which is
what the prefill/decode disaggregation A/B (benchmarks/disagg_ab.py)
improves under interference.

Every result row also flows through benchmarks/common.emit(), so with
REPRO_BENCH_JSONL set the per-request TTFT percentiles, throughput, and
cache-hit-rate land in the unified bench JSONL stream the obs reporter
renders.

The trace has more requests than engine slots, so admission/eviction and
batch-mix churn are exercised for real (max concurrent < #requests).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def make_trace(n_requests: int, rate_hz: float, seed: int, vocab: int,
               max_prompt: int = 24, max_new: int = 12):
    """Poisson arrivals (exp inter-arrival gaps), variable prompt lengths."""
    from repro.serve.scheduler import Request
    r = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for _ in range(n_requests):
        t += float(r.exponential(1.0 / rate_hz))
        plen = int(r.integers(3, max_prompt + 1))
        reqs.append(Request(
            prompt=list(r.integers(1, vocab, plen)),
            max_new_tokens=int(r.integers(2, max_new + 1)),
            arrival_time=t))
    return reqs


def make_shared_prefix_trace(n_requests: int, rate_hz: float, seed: int,
                             vocab: int, n_tenants: int = 4,
                             prefix_len: int = 16, max_tail: int = 8,
                             max_new: int = 12):
    """Multi-tenant Poisson trace: K tenants x (shared system prompt +
    unique tail).  Tenants are drawn uniformly per arrival, so every
    tenant's prefix recurs throughout the trace — the canonical
    prefix-cache workload (system prompts / few-shot headers)."""
    from repro.serve.scheduler import Request
    r = np.random.default_rng(seed)
    prefixes = [list(r.integers(1, vocab, prefix_len))
                for _ in range(n_tenants)]
    t = 0.0
    reqs = []
    for _ in range(n_requests):
        t += float(r.exponential(1.0 / rate_hz))
        tenant = int(r.integers(0, n_tenants))
        tail = list(r.integers(1, vocab, int(r.integers(1, max_tail + 1))))
        reqs.append(Request(
            prompt=prefixes[tenant] + tail,
            max_new_tokens=int(r.integers(2, max_new + 1)),
            arrival_time=t))
    return reqs


def make_decode_heavy_trace(n_requests: int, rate_hz: float, seed: int,
                            vocab: int, max_prompt: int = 6,
                            min_new: int = 12, max_new: int = 20):
    """Short prompts, long generations — the TBT-dominated regime (chat
    turns): per-request cost is almost entirely decode ticks, so the
    time-between-tokens tail IS the user experience."""
    from repro.serve.scheduler import Request
    r = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for _ in range(n_requests):
        t += float(r.exponential(1.0 / rate_hz))
        reqs.append(Request(
            prompt=list(r.integers(1, vocab, int(r.integers(3,
                                                            max_prompt + 1)))),
            max_new_tokens=int(r.integers(min_new, max_new + 1)),
            arrival_time=t))
    return reqs


def make_mixed_interference_trace(n_requests: int, rate_hz: float, seed: int,
                                  vocab: int, long_every: int = 4,
                                  long_prompt: int = 48, max_prompt: int = 6,
                                  min_new: int = 12, max_new: int = 20):
    """Decode-heavy foreground + periodic LONG-prompt interferers (every
    `long_every`-th arrival carries a `long_prompt`-token prompt with a
    short generation).  In a mixed engine each interferer's prefill chunks
    ride the same ticks as resident decodes, dragging the TBT tail — the
    exact pathology prefill/decode disaggregation removes, and what the
    disagg A/B measures."""
    from repro.serve.scheduler import Request
    r = np.random.default_rng(seed)
    t = 0.0
    reqs = []
    for i in range(n_requests):
        t += float(r.exponential(1.0 / rate_hz))
        if long_every and i % long_every == long_every - 1:
            reqs.append(Request(
                prompt=list(r.integers(1, vocab, long_prompt)),
                max_new_tokens=int(r.integers(2, 5)),
                arrival_time=t))
        else:
            reqs.append(Request(
                prompt=list(r.integers(1, vocab,
                                       int(r.integers(3, max_prompt + 1)))),
                max_new_tokens=int(r.integers(min_new, max_new + 1)),
                arrival_time=t))
    return reqs


def build_trace(args, vocab):
    preset = getattr(args, "preset", "poisson")
    if preset == "decode_heavy":
        return make_decode_heavy_trace(args.requests, args.rate, args.seed,
                                       vocab)
    if preset == "mixed_interference":
        return make_mixed_interference_trace(
            args.requests, args.rate, args.seed, vocab,
            long_every=args.long_every, long_prompt=args.long_prompt)
    if args.shared_prefix:
        return make_shared_prefix_trace(
            args.requests, args.rate, args.seed, vocab,
            n_tenants=args.shared_prefix, prefix_len=args.prefix_len,
            max_tail=args.max_tail)
    return make_trace(args.requests, args.rate, args.seed, vocab,
                      max_prompt=args.max_prompt)


def run_recipe(recipe_name: str, cfg, plan, params, args,
               prefill_chunk=None, prefix_cache=False):
    from repro.core.recipes import get_recipe
    from repro.obs.sink import Telemetry
    from repro.serve.engine import _LAT_BUCKETS, ServeConfig, ServeEngine

    recipe = get_recipe(recipe_name)
    fp8 = recipe.name == "fp8_flow"
    ecfg = ServeConfig(
        max_batch=args.max_batch, page_size=args.page_size,
        n_pages=args.n_pages, max_pages_per_req=args.max_pages,
        token_budget=args.token_budget, prefill_buckets=(16, 32, 64),
        prefill_chunk=prefill_chunk, fp8_kv=fp8, w8_weights=fp8,
        prefix_cache=prefix_cache, seed=0)
    # sink-less telemetry: the registry's serve_tbt_ms histogram gives the
    # per-TOKEN inter-token percentiles (request means hide the tail the
    # decode-heavy presets exist to expose)
    tel = Telemetry(sinks=())
    eng = ServeEngine(cfg, recipe, plan, params, ecfg, telemetry=tel)
    reqs = build_trace(args, cfg.vocab)
    assert len(reqs) > ecfg.max_batch, "trace must oversubscribe the batch"
    total_prompt = sum(len(q.prompt) for q in reqs)

    t0 = time.perf_counter()
    results = eng.run(reqs, realtime=not args.closed_loop)
    makespan = time.perf_counter() - t0

    lats = np.array([v["finish"] - v["arrival"] for v in results.values()])
    ttfts = np.array([v["first_token"] - v["arrival"]
                      for v in results.values()])
    n_tok = sum(len(v["tokens"]) for v in results.values())
    hit_tokens = sum(v["cached_tokens"] for v in results.values())
    tbt_hist = tel.registry.histogram("serve_tbt_ms", edges=_LAT_BUCKETS)
    return {
        "recipe": recipe_name,
        "preset": getattr(args, "preset", "poisson"),
        "p50_tbt_ms": tbt_hist.quantile(0.5),
        "p99_tbt_ms": tbt_hist.quantile(0.99),
        "prefill": f"chunk{prefill_chunk}" if prefill_chunk else "mono",
        "cache": "on" if prefix_cache else "off",
        "finished": len(results),
        "tok_s": n_tok / makespan,
        "mean_ttft": float(ttfts.mean()),
        "p50_lat": float(np.percentile(lats, 50)),
        "p99_lat": float(np.percentile(lats, 99)),
        "p50_ttft": float(np.percentile(ttfts, 50)),
        "p99_ttft": float(np.percentile(ttfts, 99)),
        "hit_rate": hit_tokens / total_prompt,
        "max_concurrent": eng.max_concurrent,
        "kv_bytes": eng.kv_bytes(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_moe_235b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--n-pages", type=int, default=128)
    ap.add_argument("--max-pages", type=int, default=8)
    ap.add_argument("--token-budget", type=int, default=512)
    ap.add_argument("--closed-loop", action="store_true",
                    help="ignore arrival times (saturation throughput)")
    ap.add_argument("--preset", default="poisson",
                    choices=("poisson", "decode_heavy", "mixed_interference"),
                    help="trace shape: poisson (uniform prompts), "
                         "decode_heavy (short prompts / long generations — "
                         "TBT-dominated), mixed_interference (decode-heavy "
                         "foreground + periodic long prefills, the workload "
                         "the disagg A/B measures TBT tails on)")
    ap.add_argument("--long-every", type=int, default=4,
                    help="mixed_interference: every Nth arrival is a long "
                         "prefill interferer")
    ap.add_argument("--long-prompt", type=int, default=48,
                    help="mixed_interference: interferer prompt tokens")
    ap.add_argument("--recipes", default="fp8_flow,bf16")
    ap.add_argument("--max-prompt", type=int, default=24,
                    help="longest trace prompt (chunked prefill may exceed "
                         "the largest bucket; monolithic cannot)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="bound prefill to N-token slices per tick")
    ap.add_argument("--compare-prefill", action="store_true",
                    help="run each recipe twice — monolithic vs chunked "
                         "prefill on the SAME trace — to measure the "
                         "p50/p99 TTFT effect of bounded prefill slices")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="K",
                    help="multi-tenant trace: K tenants x (shared system "
                         "prompt + unique tail) instead of fully random "
                         "prompts")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="shared system-prompt length per tenant")
    ap.add_argument("--max-tail", type=int, default=8,
                    help="longest per-request unique tail")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the radix prefix cache")
    ap.add_argument("--compare-prefix-cache", action="store_true",
                    help="run each recipe cache-on vs cache-off on the "
                         "SAME trace — measures hit rate vs TTFT/p99")
    args = ap.parse_args()

    import jax
    try:
        from benchmarks.common import emit
    except ModuleNotFoundError:      # invoked as `python benchmarks/...py`
        sys.path.insert(0, os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        from benchmarks.common import emit
    from repro.configs import get_arch
    from repro.launch.mesh import make_device_mesh, make_test_mesh
    from repro.launch.sharding import make_plan
    from repro.models.lm import init_params

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
        mesh = make_test_mesh()
    else:
        mesh = make_device_mesh()
    plan = make_plan(cfg, mesh)
    params = init_params(cfg, jax.random.key(0))

    print("recipe,preset,prefill,cache,finished,tok_s,p50_lat_s,p99_lat_s,"
          "p50_ttft_s,p99_ttft_s,p50_tbt_ms,p99_tbt_ms,hit_rate,"
          "max_concurrent,kv_MiB")

    def report(r):
        print(f"{r['recipe']},{r['preset']},{r['prefill']},{r['cache']},"
              f"{r['finished']},{r['tok_s']:.1f},"
              f"{r['p50_lat']:.3f},{r['p99_lat']:.3f},"
              f"{r['p50_ttft']:.3f},{r['p99_ttft']:.3f},"
              f"{r['p50_tbt_ms']:.2f},{r['p99_tbt_ms']:.2f},"
              f"{r['hit_rate']:.3f},"
              f"{r['max_concurrent']},{r['kv_bytes']/2**20:.1f}")
        tag = f"serve/{r['recipe']}/{r['preset']}/{r['prefill']}" \
              f"/cache_{r['cache']}"
        emit(f"{tag}/tok_s", r["tok_s"], units="tok/s")
        emit(f"{tag}/mean_ttft_ms", r["mean_ttft"] * 1e3, units="ms")
        emit(f"{tag}/p50_ttft_ms", r["p50_ttft"] * 1e3, units="ms")
        emit(f"{tag}/p99_ttft_ms", r["p99_ttft"] * 1e3, units="ms")
        emit(f"{tag}/p99_lat_ms", r["p99_lat"] * 1e3, units="ms")
        emit(f"{tag}/p50_tbt_ms", r["p50_tbt_ms"], units="ms")
        emit(f"{tag}/p99_tbt_ms", r["p99_tbt_ms"], units="ms")
        emit(f"{tag}/cache_hit_rate", r["hit_rate"],
             derived=f"{r['finished']} reqs", units="frac")

    for name in args.recipes.split(","):
        name = name.strip()
        chunk = args.prefill_chunk
        if args.compare_prefill:
            chunk = chunk or 16
            report(run_recipe(name, cfg, plan, params, args,
                              prefill_chunk=None,
                              prefix_cache=args.prefix_cache))
            report(run_recipe(name, cfg, plan, params, args,
                              prefill_chunk=chunk,
                              prefix_cache=args.prefix_cache))
        elif args.compare_prefix_cache:
            for cache in (False, True):
                report(run_recipe(name, cfg, plan, params, args,
                                  prefill_chunk=chunk, prefix_cache=cache))
        else:
            report(run_recipe(name, cfg, plan, params, args,
                              prefill_chunk=chunk,
                              prefix_cache=args.prefix_cache))


if __name__ == "__main__":
    main()
