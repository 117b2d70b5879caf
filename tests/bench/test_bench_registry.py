"""BENCHMARK.json against the files it names, and the harness finding new
pieces by name alone."""
import json
import re
import shutil
from pathlib import Path

import pytest

import bench_tiny
from bench import harness, weights

ROOT = Path(bench_tiny.ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench():
    return harness.benchmark(ROOT)


def test_names_units_and_keys():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}


def test_every_metric_has_a_reader_that_agrees():
    b = bench()
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["end_to_end"] + b["per_layer"]:
        r = harness.reader(ROOT, m["name"])
        assert (r.UNIT, r.BETTER, r.SOURCE) == (m["unit"], m["better"],
                                                m["source"]), m["name"]
        if "moves" in m:
            assert r.LAYER == m["layer"] and r.MOVES == m["moves"] in e2e
            for w in m["workloads"]:
                # each listed cell reports the metric it moves
                assert m["moves"] in {x["name"] for x in
                                      harness.metrics_for(b, w, False)}


def test_every_cell_resolves():
    b = bench()
    for w in b["workloads"]:
        cell = harness.Cell.resolve(w["name"], ROOT, b)
        assert cell.mix["kind"] in ("train", "serve")
        assert harness.metrics_for(b, w["name"], True)
        assert "setup_s" in {m["name"] for m in
                             harness.metrics_for(b, w["name"], False)}
        limits = cell.cell["limits"]
        assert limits and all(v > 0 for v in limits.values())


@pytest.mark.parametrize("name", ["dsv2lite-train-l2", "dsv2lite-serve-l5"])
def test_config_sizes_match_what_runs(name):
    c = harness.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    a = c["arch"]
    assert a["d_model"] == c["hidden_size"]
    assert a["n_heads"] == c["num_attention_heads"]
    assert a["n_kv"] == c["num_key_value_heads"]
    assert a["head_dim"] == c["v_head_dim"] == c["qk_nope_head_dim"]
    assert a["d_ff"] == -(-c["intermediate_size"] // 128) * 128
    assert a["vocab"] == c["vocab_size"]
    assert a["n_layers"] == c["num_hidden_layers"]
    assert a["n_dense_layers"] == c["first_k_dense_replace"]
    assert (a["n_experts"], a["top_k"], a["d_ff_expert"],
            a["n_shared_experts"]) == (c["n_routed_experts"],
                                       c["num_experts_per_tok"],
                                       c["moe_intermediate_size"],
                                       c["n_shared_experts"])
    assert a["rope_theta"] == c["rope_theta"]
    for k in c["reduced"]:
        assert c[k] != c["published"][k]


def test_weights_match_the_program_tree():
    import jax
    from repro.configs.base import ArchConfig
    from repro.models.lm import init_params

    c = harness.load_json(ROOT / "bench" / "configs" / "dsv2lite-serve-l5.json")
    ours = jax.eval_shape(lambda k: weights.make_tree(c, k),
                          weights.seed_key(2 ** 40 + 3))
    theirs = jax.eval_shape(lambda k: init_params(ArchConfig(**c["arch"]), k),
                            jax.random.key(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for x, y in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype)


def test_new_pieces_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, a cell and a metric added as new
    files (and entries) under a fresh root, with no existing file edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    b = bench()
    cfg = harness.load_json(ROOT / "bench" / "configs" / "dsv2lite-train-l2.json")
    cfg["name"] = "other-l2"
    (tmp_path / "bench" / "configs" / "other-l2.json").write_text(
        json.dumps(cfg))
    mix = harness.load_json(ROOT / "bench" / "traffic" / "pack4k.json")
    mix["seq_len"] = 8192
    mix["batch"] = 1
    (tmp_path / "bench" / "traffic" / "pack8k.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "cells" / "other-l2.pack8k.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    (tmp_path / "bench" / "metrics" / "tokens_per_step.py").write_text(
        'LAYER = "step"\nUNIT = "tokens"\nBETTER = "higher"\n'
        'MOVES = "train_tokens_per_s"\nSOURCE = "host_clock"\n\n\n'
        'def read(run):\n    return run.record["tokens_per_step"]\n')
    b["configs"].append({"name": "other-l2", "source": "x",
                         "file": "bench/configs/other-l2.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "other-l2.pack8k", "config": "other-l2",
                           "traffic": "pack8k", "chips": 1, "why": "x"})
    for m in b["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("other-l2.pack8k")
    b["per_layer"].append({"name": "tokens_per_step", "unit": "tokens",
                           "better": "higher", "source": "host_clock",
                           "layer": "step", "moves": "train_tokens_per_s",
                           "workloads": ["other-l2.pack8k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.Cell.resolve("other-l2.pack8k", tmp_path)
    assert cell.cfg["name"] == "other-l2" and cell.mix["seq_len"] == 8192
    got = [m["name"] for m in harness.metrics_for(harness.benchmark(tmp_path),
                                                  "other-l2.pack8k", True)]
    assert got == ["tokens_per_step"]
    r = harness.reader(tmp_path, "tokens_per_step")
    run = harness.Run(cell, {"tokens_per_step": 8192}, None, {})
    assert r.read(run) == 8192
    # the cell that was there before still resolves as it did
    assert harness.Cell.resolve("dsv2lite-train-l2.pack4k",
                                tmp_path).mix["seq_len"] == 4096
