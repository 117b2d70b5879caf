"""Smoke run of the FP8-flow MoE main path on TPU chips, in one process.

    python3 chip_smoke.py              # one chip: kernels, train, serve
    python3 chip_smoke.py --chips 4    # four chips: the expert-parallel step

Model: DeepSeek-V2-Lite (the paper's Fig. 6 convergence model) at its
published widths -- d_model 2048, 16 heads of 128, 64 routed experts of width
1408 with top-6 routing plus 2 shared experts, a first dense layer of d_ff
10880 -- cut to fit one 16 GB v5e chip:

    dataclasses.replace(get_arch("deepseek_v2_lite"), n_layers=2, vocab=12800)

that is one dense and one MoE layer (of 27), and the first eighth of the
102,400-token vocabulary, from which every token id is drawn.  Two departures
from the published model come with the config file itself: MLA attention is
simplified to GQA, and the dense d_ff 10944 is rounded to 10880 (a multiple
of 128).  Weights are random, made from --seed.

Phases on one chip, each freeing its arrays before the next starts:
  kernels  every kernels/ops.py wrapper, compiled for the chip, at these
           widths against its kernels/ref.py oracle: payload, scale and
           output bits must match
  serve    8 requests (prompts of 200-400 tokens, 32 new tokens each)
           through launch/serve.run with W8 expert weights and FP8 KV pages;
           every request finishes, and one request's prefill logits agree
           with models/lm.forward on the same prompt within the FP8
           recipe's own error
  train    TRAIN_STEPS fp8_flow steps at B=2, S=4096 through
           launch/train.run, once on the default XLA path and once with
           Recipe.use_pallas=True; losses finite, falling, and the two paths
           within TRAIN_PATH_RTOL of each other

With --chips 4 only the expert-parallel train step runs: EP_STEPS steps on a
(data=1, model=4) mesh (16 experts per chip, FP8 dispatch all-to-all),
against the same steps on one of the four devices, run first and freed.

Every phase prints its device, compile time apart from step time, and the
device's bytes in use and its peak since the process started; the phases run
in the order of their footprint, so each peak is that phase's own.  Those
times are smoke timings, not measurements.  No phase's failure is caught.
The last line of stdout is the JSON verdict; without a TPU the script exits
non-zero and prints none.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                    # noqa: E402
import jax.numpy as jnp                       # noqa: E402
import numpy as np                            # noqa: E402

from repro.configs import get_arch            # noqa: E402
from repro.core.fp8 import TILE               # noqa: E402
from repro.core.quant import quantize         # noqa: E402
from repro.core.recipes import get_recipe     # noqa: E402
from repro.kernels import ops, ref            # noqa: E402
from repro.launch import serve, train         # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_device_mesh, make_test_mesh  # noqa: E402
from repro.models.lm import forward, paged_prefill  # noqa: E402
from repro.serve.engine import ServeConfig    # noqa: E402
from repro.serve.paged_kv import init_paged_cache  # noqa: E402
from repro.serve.scheduler import Request     # noqa: E402

SEQ_LEN, BATCH = 4096, 2
TRAIN_STEPS = 5
EP_STEPS = 3
# The XLA and Pallas paths quantize the same values to the same po2-scaled
# e4m3 grid, but round twice where the kernels round once: the XLA Dgrad
# epilogue and SwiGLU round to bf16 before re-quantizing.  That moves an FP8
# code by at most one step now and then, the noise an FP8 recipe already
# carries; tests/test_dist.py holds an FP8 run to 1% of an f32 one.
TRAIN_PATH_RTOL = 1e-2
# One device against four: the same math, but each chip routes a quarter of
# the tokens, so capacity drops differ (capacity_factor 1.25 is applied per
# source shard), reductions run in another order, and the Wgrad transpose
# blocks hold other tokens.  A fraction of a percent of assignments may move;
# a sharding bug (a wrong expert slice, a missing all-to-all) moves the loss
# by whole units.
EP_LOSS_RTOL = 1e-2
EP_GNORM_RTOL = 5e-2
# Prefill logits against the one-shot forward.  Both run fp8_flow on the
# same FP8 weights (W8 is bitwise the block quantization forward applies on
# the fly) and attend over the chunk's own bf16 K/V, but the two programs
# project, attend and reduce in another order.  Where a bf16 rounding
# differs, an activation lands on the neighbouring e4m3 code (2^-3
# relative), and through the router it can swap one of the six experts, so
# the paths agree only to within FP8 noise.  The bound is the recipe's own
# error: prefill may differ from forward by no more than forward's fp8_flow
# logits differ from its bf16-recipe logits (norm of the difference over
# norm of the reference).  A paging, position or weight fault lands far
# outside it.
SERVE_ERR_RECIPE = "bf16"


def smoke_config():
    return dataclasses.replace(get_arch("deepseek_v2_lite"), n_layers=2,
                               vocab=12800)


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------
_compile = {"s": 0.0, "n": 0}


def _on_duration(event: str, secs: float, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["s"] += secs
        _compile["n"] += 1


def _mem(devices=None) -> str:
    out = []
    for d in devices or jax.local_devices()[:1]:
        st = d.memory_stats()
        if st is None:              # the CPU backend keeps no such counters
            out.append(f"{d.id}:n/a")
        else:
            out.append(f"{d.id}:in_use={st['bytes_in_use']} "
                       f"peak={st['peak_bytes_in_use']}")
    return "bytes " + " ".join(out)


def _log(msg: str):
    print(msg, flush=True)


def _check(ok: bool, what):
    """A smoke verdict: raises (unlike ``assert``, also under ``-O``)."""
    if not ok:
        raise RuntimeError(f"smoke check failed: {what}")


class _Phase:
    """Prints a phase's compile time, wall time and device memory.  A phase
    keeps its arrays in the locals of a function called inside the block, so
    they are freed when it returns; the memory line comes after that."""

    def __init__(self, name: str, devices=None):
        self.name, self.devices = name, devices

    def __enter__(self):
        self.c0, self.n0 = _compile["s"], _compile["n"]
        self.t0 = time.perf_counter()
        _log(f"[{self.name}] start on {jax.devices()[0].device_kind} "
             f"x{len(jax.devices())}")
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            wall = time.perf_counter() - self.t0
            gc.collect()
            _log(f"[{self.name}] done: wall {wall:.1f}s, of which compile "
                 f"{_compile['s'] - self.c0:.1f}s in "
                 f"{_compile['n'] - self.n0} programs (smoke timings, not "
                 f"measurements); after freeing: {_mem(self.devices)}")
        return False


# ---------------------------------------------------------------------------
# (a) kernels
# ---------------------------------------------------------------------------
def _x(rng, *shape, spread=0.5, scale=1.0):
    v = rng.normal(size=shape) * np.exp(rng.normal(size=shape) * spread)
    return jnp.asarray((v * scale).astype(np.float32))


def _diff(got, want) -> int:
    """Number of elements whose bits differ."""
    g, w = np.asarray(got), np.asarray(want)
    _check(g.shape == w.shape and g.dtype == w.dtype,
           (g.shape, g.dtype, w.shape, w.dtype))
    u = np.dtype(f"u{g.dtype.itemsize}")
    return int(np.count_nonzero(g.view(u) != w.view(u)))


def phase_kernels(*, D: int = 2048, F: int = 1408, E: int = 8, C: int = 384,
                  seed: int = 0) -> dict:
    """Every ops.py wrapper against its ref.py oracle on expert-layer shapes:
    E experts of capacity C, model width D, expert width F.  Returns
    {wrapper: differing elements}; raises if any is nonzero."""
    with _Phase("kernels"):
        return _kernels(D, F, E, C, seed)


def _kernels(D, F, E, C, seed):
    rng = np.random.default_rng(seed)
    M = E * C
    x = _x(rng, M, D, spread=1.5).astype(jnp.bfloat16)
    h = _x(rng, M, 2 * F).astype(jnp.bfloat16)
    qx = quantize(_x(rng, E, C, D), (1, 1, TILE), tag="smoke")
    qh = quantize(_x(rng, E, C, F), (1, 1, TILE), tag="smoke")
    qw13 = quantize(_x(rng, E, D, 2 * F, spread=0.3, scale=0.05),
                    (1, TILE, TILE), tag="smoke")
    qw2 = quantize(_x(rng, E, F, D, spread=0.3, scale=0.05),
                   (1, TILE, TILE), tag="smoke")
    qa = quantize(_x(rng, E, D, C), (1, 1, TILE), tag="smoke")
    qb = quantize(_x(rng, E, 2 * F, C, scale=0.1), (1, 1, TILE), tag="smoke")
    # live rows per expert: empty, full, and mid-tile counts
    mm = jnp.asarray(rng.integers(0, C + 1, E), jnp.int32).at[:2].set(
        jnp.asarray([0, C], jnp.int32))
    n_out = M + M // 2
    row_map = np.full((n_out,), -1, np.int32)
    row_map[rng.choice(n_out, M, replace=False)] = rng.permutation(M)
    row_map = jnp.asarray(row_map)

    q = ops.quantize_rowwise(x)
    cases = {
        "quantize_rowwise": ((q.data, q.scale), ref.quantize_rowwise_ref(x)),
        "fused_swiglu_quant": (ops.fused_swiglu_quant(h),
                               ref.fused_swiglu_quant_ref(h)),
        "fp8_transpose": (ops.fp8_transpose(q),
                          ref.fp8_transpose_ref(q.data, q.scale)),
        "fused_permute_pad": (
            ops.fused_permute_pad(q, row_map, n_out),
            ref.fused_permute_pad_ref(q.data, q.scale, row_map, n_out)),
        "grouped_gemm_fp8": (
            ops.grouped_gemm_fp8(qx, qw13),
            ref.grouped_gemm_fp8_ref(qx.data, qx.scale, qw13.data,
                                     qw13.scale)),
        "grouped_gemm_fp8_quant_out": (
            ops.grouped_gemm_fp8_quant_out(qh, qw2),
            ref.grouped_gemm_fp8_quant_out_ref(qh.data, qh.scale, qw2.data,
                                               qw2.scale)),
        "grouped_gemm_nt_fp8": (
            ops.grouped_gemm_nt_fp8(qa, qb),
            ref.grouped_gemm_nt_fp8_ref(qa.data, qa.scale, qb.data,
                                        qb.scale)),
        "grouped_gemm_fp8_masked": (
            ops.grouped_gemm_fp8_masked(qx, qw13, mm),
            ref.masked_grouped_gemm_fp8_ref(qx.data, qx.scale, qw13.data,
                                            qw13.scale, mm)),
        "grouped_gemm_fp8_masked_quant_out": (
            ops.grouped_gemm_fp8_masked_quant_out(qh, qw2, mm),
            ref.masked_grouped_gemm_fp8_quant_out_ref(
                qh.data, qh.scale, qw2.data, qw2.scale, mm)),
        "grouped_gemm_nt_fp8_masked": (
            ops.grouped_gemm_nt_fp8_masked(qa, qb, mm),
            ref.masked_grouped_gemm_nt_fp8_ref(qa.data, qa.scale, qb.data,
                                               qb.scale, mm)),
        "grouped_gemm_swiglu_quant_masked": (
            ops.grouped_gemm_swiglu_quant_masked(qx, qw13, mm),
            ref.masked_grouped_gemm_swiglu_quant_ref(
                qx.data, qx.scale, qw13.data, qw13.scale, mm)),
    }
    diffs = {}
    for name, (got, want) in cases.items():
        got = (got.data, got.scale) if hasattr(got, "scale") else got
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        diffs[name] = sum(_diff(g, w) for g, w in zip(got, want, strict=True))
        shapes = " ".join(str(tuple(g.shape)) for g in got)
        _log(f"[kernels] {name} {shapes}: {diffs[name]} elements differ")
    bad = {k: v for k, v in diffs.items() if v}
    _check(not bad, f"kernel outputs differ from their oracles: {bad}")
    return diffs


# ---------------------------------------------------------------------------
# (b) train
# ---------------------------------------------------------------------------
def _train(cfg, recipe, *, steps, seq_len, batch, seed, mesh=None):
    state, hist, info = train.run(cfg, recipe=recipe, steps=steps,
                                  seq_len=seq_len, global_batch=batch,
                                  seed=seed, mesh=mesh, log_fn=_log)
    del state
    for h in hist:
        _log(f"[train]   step {h['step']}: loss {h['loss']:.6f} "
             f"grad_norm {h['grad_norm']:.6f}, step {h['device_ms']:.1f}ms "
             f"(smoke timing)")
    return hist


def phase_train(cfg, *, steps: int = TRAIN_STEPS, seq_len: int = SEQ_LEN,
                batch: int = BATCH, seed: int = 0,
                paths=("xla", "pallas")) -> dict:
    """fp8_flow through launch/train.run on the XLA path and the Pallas path;
    returns {path: [loss per step]}."""
    losses = {}
    for path in paths:
        recipe = dataclasses.replace(get_recipe("fp8_flow"),
                                     use_pallas=path == "pallas")
        with _Phase(f"train/{path}"):
            hist = _train(cfg, recipe, steps=steps, seq_len=seq_len,
                          batch=batch, seed=seed)
        ls = [h["loss"] for h in hist]
        _check(all(math.isfinite(v) for v in ls), f"{path} losses {ls}")
        _check(ls[-1] < ls[0], f"{path} loss did not fall: {ls}")
        losses[path] = ls
    if len(losses) == 2:
        rel = max(abs(a - b) / abs(b) for a, b in zip(*losses.values()))
        _log(f"[train] pallas vs xla: max relative loss difference "
             f"{rel:.3e} (limit {TRAIN_PATH_RTOL})")
        _check(rel <= TRAIN_PATH_RTOL, (losses, rel))
    return losses


# ---------------------------------------------------------------------------
# (c) serve
# ---------------------------------------------------------------------------
def phase_serve(cfg, *, n_requests: int = 8, prompt_len=(200, 400),
                max_new: int = 32, buckets=(256, 512), page_size: int = 16,
                seed: int = 0) -> dict:
    """ServeEngine through launch/serve.run (W8 experts, FP8 KV pages), then
    one request's prefill logits against models/lm.forward."""
    recipe = get_recipe("fp8_flow")
    pages_per_req = -(-(max(buckets) + max_new) // page_size)
    ecfg = ServeConfig(
        max_batch=n_requests, page_size=page_size,
        n_pages=n_requests * pages_per_req + 1,
        max_pages_per_req=pages_per_req,
        token_budget=n_requests * (max(buckets) + max_new),
        prefill_buckets=tuple(buckets), fp8_kv=True, w8_weights=True,
        seed=seed)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=[int(t) for t in rng.integers(
                1, cfg.vocab, int(rng.integers(*prompt_len)))],
                    max_new_tokens=max_new)
            for _ in range(n_requests)]
    with _Phase("serve"):
        return _serve(cfg, recipe, ecfg, reqs, seed)


def _serve(cfg, recipe, ecfg, reqs, seed):
    results, engine, params = serve.run(cfg, recipe=recipe, ecfg=ecfg,
                                        requests=reqs, seed=seed,
                                        log_fn=_log)
    n_tok = sum(len(v["tokens"]) for v in results.values())
    _log(f"[serve] {len(results)}/{len(reqs)} requests, {n_tok} tokens, "
         f"ticks={results.stats['ticks']}")
    max_new = reqs[0].max_new_tokens          # no EOS: every request runs out
    _check(len(results) == len(reqs) and all(
        len(v["tokens"]) == max_new for v in results.values()),
        {k: len(v["tokens"]) for k, v in results.items()})

    # prefill logits of request 0 against the one-shot forward on the
    # same right-padded bucket (causal: padding never reaches the
    # prompt's last position)
    prompt = reqs[0].prompt
    L = len(prompt)
    bucket = min(b for b in ecfg.prefill_buckets if b >= L)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :L] = prompt
    toks = jnp.asarray(toks)
    plan = engine.plan
    pages = ecfg.max_pages_per_req
    pools = init_paged_cache(cfg, pages + 1, ecfg.page_size, fp8_kv=True)
    ptrow = jnp.arange(1, pages + 1, dtype=jnp.int32)
    lg, _ = jax.jit(lambda p, pl, pt, t, n: paged_prefill(
        cfg, recipe, plan, p, pl, pt, t, n))(engine.params, pools, ptrow,
                                            toks, jnp.int32(L))

    def fwd(rcp):
        out, _ = jax.jit(lambda p, t: forward(
            cfg, rcp, plan, p, {"tokens": t}, compute_loss=False))(params, toks)
        return np.asarray(out[0, L - 1], np.float32)

    def err(got, want):
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))

    a = np.asarray(lg[0, -1], np.float32)
    b = fwd(recipe)
    serve_err = err(a, b)
    recipe_err = err(b, fwd(get_recipe(SERVE_ERR_RECIPE)))
    _log(f"[serve] prefill vs forward logits (prompt {L} tokens): relative "
         f"error {serve_err:.3e}; limit {recipe_err:.3e}, forward's "
         f"{recipe.name} against its {SERVE_ERR_RECIPE} logits")
    _check(serve_err <= recipe_err, (serve_err, recipe_err))
    return {"finished": len(results), "tokens": n_tok,
            "serve_err": serve_err, "recipe_err": recipe_err}


# ---------------------------------------------------------------------------
# --chips 4: the expert-parallel step against one device
# ---------------------------------------------------------------------------
def phase_ep(cfg, *, steps: int = EP_STEPS, seq_len: int = SEQ_LEN,
             batch: int = BATCH, seed: int = 0, model: int = 4) -> dict:
    """``steps`` fp8_flow steps on one device, then on a (data=1,
    model=``model``) mesh; loss and grad norm of each step must agree."""
    recipe = get_recipe("fp8_flow")
    devices = jax.devices()[:model]
    runs = {}
    for name, mesh in (("one_device", make_test_mesh()),
                       ("ep", make_device_mesh(model=model))):
        with _Phase(f"ep/{name}", devices=devices):
            _log(f"[ep/{name}] mesh {dict(mesh.shape)}, "
                 f"{cfg.n_experts // mesh.shape['model']} experts per device")
            runs[name] = _train(cfg, recipe, steps=steps, seq_len=seq_len,
                                batch=batch, seed=seed, mesh=mesh)
    worst = {"loss": 0.0, "grad_norm": 0.0}
    for a, b in zip(runs["ep"], runs["one_device"], strict=True):
        for k in worst:
            worst[k] = max(worst[k], abs(a[k] - b[k]) / abs(b[k]))
    _log(f"[ep] {model} devices vs one: max relative difference loss "
         f"{worst['loss']:.3e} (limit {EP_LOSS_RTOL}), grad norm "
         f"{worst['grad_norm']:.3e} (limit {EP_GNORM_RTOL})")
    _check(worst["loss"] <= EP_LOSS_RTOL
           and worst["grad_norm"] <= EP_GNORM_RTOL, (runs, worst))
    return worst


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the expert-parallel train step across "
                         "four chips, against one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    cfg = smoke_config()
    _log(f"[smoke] {cfg.name} cut: n_layers={cfg.n_layers} "
         f"vocab={cfg.vocab}, {cfg.n_params() / 1e9:.3f}B params; "
         f"{dev.device_kind} x{len(jax.devices())}")

    if args.chips == 4:
        phase_ep(cfg, seed=args.seed)
    else:
        phase_kernels(seed=args.seed)
        phase_serve(cfg, seed=args.seed)
        phase_train(cfg, seed=args.seed)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
