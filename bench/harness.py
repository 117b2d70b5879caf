"""The benchmark harness: finds a cell's pieces by name and runs it once.

Everything a cell needs is found from ``BENCHMARK.json`` by name, so a
later change adds a configuration, a traffic mix, a cell or a metric by
adding files:

  bench/configs/<config>.json     sizes as run, source, reduced, reference
  bench/traffic/<traffic>.json    parameters of the one generator (gen.py)
  bench/cells/<workload>.json     the cell's own numbers: rate, limits
  bench/metrics/<metric>.py       one reader per metric (end-to-end and
                                  per-layer alike)
  bench/reference/<name>.py       the plain reference a configuration names,
                                  and, if it defines leaves(cfg, padded),
                                  the parameter tree (bench/weights.py)

A reader module declares LAYER, UNIT, BETTER, MOVES and SOURCE as
``BENCHMARK.json`` has them and defines ``read(run) -> float | None``;
``run`` is a ``Run``.  A reader that finds nothing to read returns None and
the metric is left out of the line.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

ROOT = Path(__file__).resolve().parents[1]
# set by Cell.resolve on the configuration: the root it was found under, where
# weights.leaves looks for its reference's own leaf list
ROOT_KEY = "_root"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r}")


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(root: Path, cfg: dict):
    return load_module(root / "bench" / "reference" / f"{cfg['reference']}.py",
                       f"bench_reference_{cfg['reference']}")


def metrics_for(bench: dict, workload: str, per_layer: bool):
    """The metric entries a cell reports: with trace off its end-to-end
    metrics, with trace on its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not per_layer:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])
            and (m["moves"] in names)]


def reader(root: Path, name: str):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_"))


@dataclasses.dataclass
class Cell:
    """A cell resolved from its name: the JSON of each piece."""
    name: str
    workload: dict
    cfg: dict
    mix: dict
    cell: dict
    root: Path

    @classmethod
    def resolve(cls, name: str, root: Path = ROOT, bench: dict = None):
        bench = bench or benchmark(root)
        w = find(bench["workloads"], name, "workload")
        cfg_entry = find(bench["configs"], w["config"], "config")
        cfg = load_json(root / cfg_entry["file"])
        cfg[ROOT_KEY] = str(root)
        return cls(name, w, cfg,
                   load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                   load_json(root / "bench" / "cells" / f"{name}.json"), root)


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    record: dict                   # the run's host-side record
    trace: Any                     # tracefold.Reduced, or None
    peaks: dict


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
_compile = {"s": 0.0, "n": 0}
_listening = []


def listen_compiles():
    import jax
    if not _listening:
        def on(event: str, secs: float, **_):
            if event in COMPILE_EVENTS:
                _compile["s"] += secs
                _compile["n"] += 1
        jax.monitoring.register_event_duration_secs_listener(on)
        _listening.append(True)


def compiles() -> Dict[str, float]:
    return dict(_compile)


# ---------------------------------------------------------------------------
def device_info(chips: int, require_chip: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_chip and (d0.platform == "cpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} accelerator chip(s); JAX found "
                     f"{len(devs)} {d0.platform} device(s)")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, t_start: Optional[float] = None,
             require_chip: bool = True, peaks: Optional[dict] = None,
             fault: Optional[str] = None,
             edit: Optional[Callable[[Cell], None]] = None) -> dict:
    """Run one cell once; returns the result object of the last line.

    ``require_chip=False``, ``peaks``, ``fault`` and ``edit`` (which may
    shrink the resolved cell) exist for the tests that drive a run on the
    CPU; the command line never sets them."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = benchmark(root)
    cell = Cell.resolve(name, root, bench)
    if edit is not None:
        edit(cell)
    device = device_info(cell.workload["chips"], require_chip)
    if peaks is None:
        from bench.peaks import peaks_for
        peaks = peaks_for(device["kind"])
    listen_compiles()
    kind = cell.mix["kind"]
    if kind == "train":
        from bench import train_cell as kind_run
    elif kind == "serve":
        from bench import serve_cell as kind_run
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    rec = kind_run.run(cell, seed=seed, seconds=seconds, trace=trace,
                     t_start=t_start, fault=fault)
    device["memory_peak_bytes"] = rec.pop("memory_peak_bytes")
    reduced = rec.pop("reduced", None)
    if trace:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
    run = Run(cell, rec, reduced, peaks)
    metrics = {}
    for m in metrics_for(bench, name, per_layer=trace):
        v = reader(root, m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    out = {"correct": rec["correct"], "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = reduced.breakdown
    out["compared"] = rec["compared"]
    gc.collect()
    return out


def print_compared(compared: dict):
    """The numbers compared, each beside its limit, as the last lines of
    standard error."""
    for k, v in compared.items():
        log(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
