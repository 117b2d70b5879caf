"""A training cell: the FP8-flow train step as ``launch/train.run`` builds it
(same ``make_train_step``, state shardings and donation), fed the traffic
mix's packed rows instead of ``data/pipeline.make_batch``.

Set-up makes the state on the device in one jitted call from the seed
(weights from ``bench.weights``, AdamW state by the program's
``init_state``), compiles the step, and drives it through three steps
through the window's own call and feed; what those steps did is read then:
each step's loss, the first gradient as the optimizer got it (its first
moment over 1 - b1), and the parameters' change over the three steps.  The
window then runs the same compiled step, syncing on each step's metrics as
``train/loop.run`` does.  After the window the state is freed and the plain
reference repeats the three steps in float32 for ``check.train_numbers``,
and counts the routed assignments that its capacity drops on them (logged,
not compared).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, gen, weights
from bench.harness import compiles, log, memory_peak_bytes, reference

CHECK_STEPS = 3


@dataclasses.dataclass
class Built:
    cell: object
    mesh: object
    opt: object
    state_sh: object
    batch_sh: object
    init: object          # jitted: key -> state
    step: object          # compiled step (donates its state)


def build(cell, fault: Optional[str] = None) -> Built:
    from repro.configs.base import ArchConfig
    from repro.core.recipes import get_recipe
    from repro.launch import sharding
    from repro.launch.mesh import make_device_mesh
    from repro.launch.train import state_shardings
    from repro.optim import adamw
    from repro.train.train_step import make_train_step

    cfg, mix = cell.cfg, cell.mix
    arch = ArchConfig(**cfg["arch"])
    o = cfg["optimizer"]
    opt = adamw.AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                            weight_decay=o["weight_decay"],
                            grad_clip=o["grad_clip"])
    mesh = make_device_mesh()
    plan = sharding.make_plan(arch, mesh)

    def init(key):
        params = weights.make_tree(cfg, key)
        return {"params": params, "opt": adamw.init_state(opt, params)}

    key0 = weights.seed_key(0)
    state_sh = state_shardings(arch, mesh, jax.eval_shape(init, key0), None)
    B, S = mix["batch"], mix["seq_len"]
    shapes = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
              "mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
    batch_sh = sharding.batch_specs(mesh, shapes, plan.dp_axes)
    fn = make_train_step(arch, get_recipe(cfg["recipe"]), plan, opt,
                         total_steps=o["total_steps"],
                         warmup_steps=o["warmup_steps"])
    if fault == "stale_state":      # a step that hands back its input state
        fn = (lambda f: lambda s, b: (s, f(s, b)[1]))(fn)
    donate = () if fault == "stale_state" else (0,)
    with jax.set_mesh(mesh):
        state_shapes = jax.eval_shape(init, key0)
        step = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                       out_shardings=(state_sh, None),
                       donate_argnums=donate).lower(
            state_shapes, shapes).compile()
    return Built(cell, mesh, opt, state_sh, batch_sh,
                 jax.jit(init, out_shardings=state_sh), step)


def feed(b: Built, rows: gen.PackedRows, i: int, fault: Optional[str]):
    batch = rows.batch(i)
    if fault == "half_batch":           # half the rows out of the mean
        batch["mask"][batch["mask"].shape[0] // 2:] = 0.0
    return jax.device_put(batch, b.batch_sh)


def one_step(b: Built, state, batch):
    """The window's call: one step, then the blocking fetch of its metrics
    (the sync ``train/loop.run`` makes every step)."""
    state, m = b.step(state, batch)
    return state, jax.device_get(m)


@jax.jit
def _leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def _paths(cfg):
    return [".".join(lf.path) for lf in weights.leaves(cfg)]


def _by_path(cfg, tree):
    return [_get(tree, lf.path) for lf in weights.leaves(cfg)]


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _change_norms(cfg, master, key):
    """|master - initial| per leaf, the initial leaf drawn again from the
    seed one leaf at a time (no second copy of the state)."""
    out = []
    for i, lf in enumerate(weights.leaves(cfg)):
        f = jax.jit(lambda m, k, _lf=lf, _i=i: jnp.sqrt(jnp.sum(jnp.square(
            m - weights.draw(_lf, k, _i, jnp.float32)))))
        out.append(float(f(_get(master, lf.path), key)))
    return out


def program_first_steps(b: Built, state, rows, key, fault=None):
    """Steps 0..2 through the window's call; returns (state, readings)."""
    cfg = b.cell.cfg
    names = _paths(cfg)
    losses, grad = [], None
    for i in range(CHECK_STEPS):
        state, m = one_step(b, state, feed(b, rows, i, fault))
        losses.append(float(m["loss"]))
        if i == 0:
            g = jax.device_get(_leaf_norms(_by_path(cfg, state["opt"]["m"])))
            grad = {n: float(x) / (1.0 - b.opt.b1) for n, x in zip(names, g)}
    change = dict(zip(names, _change_norms(cfg, state["opt"]["master"], key)))
    return state, {"losses": losses, "grad": grad, "change": change}


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------
def reference_readings(cell, seed: int, quant=None) -> dict:
    """Three float32 AdamW steps of the plain reference on the same rows."""
    cfg = cell.cfg
    ref = reference(cell.root, cfg)
    rows = gen.PackedRows(cell.mix, cfg["vocab_size"], seed)
    key = weights.seed_key(seed)
    o = cfg["optimizer"]
    names = _paths(cfg)
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: weights.make_tree(cfg, k, padded=False,
                                                     dtype=jnp.float32))(key)
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        m, v = zeros(params), zeros(params)

        def step(params, m, v, batch, lr, t):
            (lv, dropped), g = jax.value_and_grad(
                lambda p: ref.loss(cfg, p, batch, quant=quant),
                has_aux=True)(params)
            params, m, v, g = ref.adamw(o, params, g, m, v, lr, t)
            return params, m, v, lv, dropped, _leaf_norms(
                [_get(g, lf.path) for lf in weights.leaves(cfg, False)])

        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        losses, grad, dropped = [], None, []
        for i in range(CHECK_STEPS):
            batch = {k: jnp.asarray(x) for k, x in rows.batch(i).items()}
            params, m, v, lv, d, gn = jstep(params, m, v, batch,
                                            jnp.float32(ref.lr_at(o, i)),
                                            jnp.float32(i + 1))
            losses.append(float(lv))
            dropped.append(int(d))
            if i == 0:
                grad = dict(zip(names, (float(x) for x in gn)))
        del m, v
        change = []
        for i, lf in enumerate(weights.leaves(cfg, padded=False)):
            f = jax.jit(lambda p, k, _lf=lf, _i=i: jnp.sqrt(jnp.sum(
                jnp.square(p - weights.draw(_lf, k, _i, jnp.float32)))))
            change.append(float(f(_get(params, lf.path), key)))
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    routed = n_moe * cell.mix["batch"] * cell.mix["seq_len"] \
        * cfg["num_experts_per_tok"]
    kept = routed - float(np.mean(dropped))
    return {"losses": losses, "grad": grad, "change": dict(zip(names, change)),
            "kept_rows": kept, "drop_share": 1.0 - kept / routed}


# ---------------------------------------------------------------------------
def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        fault: Optional[str] = None) -> dict:
    from bench import tracefold

    cfg, mix = cell.cfg, cell.mix
    marks = [("start", time.perf_counter() - t_start)]
    b = build(cell, fault)
    marks.append(("step compiled", time.perf_counter() - t_start))
    key = weights.seed_key(seed)
    rows = gen.PackedRows(mix, cfg["vocab_size"], seed)
    with jax.set_mesh(b.mesh):
        state = b.init(key)
        jax.block_until_ready(state)
        marks.append(("state made", time.perf_counter() - t_start))
        state, prog = program_first_steps(b, state, rows, key, fault)
    tokens_per_step = mix["batch"] * mix["seq_len"]
    setup_s = time.perf_counter() - t_start
    marks.append(("checked steps read", setup_s))
    log("[train] set-up " + ", ".join(f"{n} at {t:.1f}s" for n, t in marks))
    c0 = compiles()

    # ---- the window ----
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    t_from, t_len = 0.3 * seconds, min(5.0, 0.4 * seconds)
    losses, traced_steps, tracing, done_tracing = [], 0, False, False
    win = None
    i = CHECK_STEPS
    with jax.set_mesh(b.mesh):
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            if trace and not tracing and not done_tracing and now >= t_from:
                jax.profiler.start_trace(trace_dir)
                win = jax.profiler.TraceAnnotation(tracefold.WINDOW_SPAN)
                win.__enter__()
                tracing, t_trace = True, time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/step"):
                state, m = one_step(b, state, feed(b, rows, i, fault))
            losses.append(float(m["loss"]))
            i += 1
            if tracing:
                traced_steps += 1
                if time.perf_counter() - t_trace >= t_len:
                    win.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing, done_tracing = False, True
        window_s = time.perf_counter() - t0
    if tracing:
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    steps = len(losses)
    window_compiles = compiles()["n"] - c0["n"]
    peak = memory_peak_bytes()
    hlo = b.step.as_text() if trace else None
    del state
    b = None
    gc.collect()

    reduced = None
    if trace:
        ops, spans = tracefold.read_xplane(tracefold.find_xplane(trace_dir),
                                           [hlo])
        reduced = tracefold.reduce(ops, spans)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- correctness: the reference repeats the set-up's three steps ----
    ref = reference_readings(cell, seed)
    numbers = dict(check.train_numbers(prog, ref),
                   window_compiles=window_compiles)
    correct, compared = check.judge(numbers, check.limits(cell))
    failed = sum(1 for x in losses if not math.isfinite(x))
    log(f"[train] {steps} steps in {window_s:.3f}s, "
        f"{steps * tokens_per_step / window_s:.1f} tokens/s; set-up "
        f"{setup_s:.1f}s; compiles in window {window_compiles}; peak "
        f"{peak} B; program losses {prog['losses']} reference "
        f"{ref['losses']}; routed rows kept under capacity (reference, "
        f"mean of the checked steps) {ref['kept_rows']:.0f} of "
        f"{ref['kept_rows'] / (1 - ref['drop_share']):.0f}; worst grad "
        f"leaves {check.worst_leaves(prog, ref)}")
    kept = ref["kept_rows"]
    return {"kind": "train", "setup_s": setup_s, "window_s": window_s,
            "steps": steps, "tokens": steps * tokens_per_step,
            "tokens_per_step": tokens_per_step,
            "traced_steps": traced_steps, "compile_s": c0["s"],
            "window_compiles": window_compiles, "peak_bytes": peak,
            "memory_peak_bytes": peak, "kept_rows": kept,
            "attempted": steps, "failed": failed, "correct": correct,
            "compared": compared, "reduced": reduced}
