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


SHARE_REFERENCE = '''"""A share configuration's reference: the default tree, then
a router bias."""
from bench import weights


def leaves(cfg, padded):
    nm = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    E = cfg["published"]["n_routed_experts"]
    return weights.default_leaves(cfg, padded) + [
        weights.Leaf(("layers", "e_score_correction_bias"), (nm, E), (nm, E),
                     "float32", 0.02)]
'''


def share_cfg(cfg):
    """4 of 16 routed experts held, ids 4..7, and a reference of its own."""
    cfg.update({"name": "share-l2", "n_routed_experts": 4,
                "first_routed_expert": 4, "reference": "share_ref",
                "reduced": cfg["reduced"] + ["n_routed_experts"]})
    cfg["published"]["n_routed_experts"] = 16
    cfg["arch"]["n_experts"] = 4
    return cfg


def test_share_config_with_its_own_leaves_is_found_by_name(tmp_path):
    """A configuration that holds a share of its routed experts, and whose
    reference brings its own leaf list, added as new files only."""
    import jax

    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    cfg = share_cfg(harness.load_json(
        ROOT / "bench" / "configs" / "dsv2lite-train-l2.json"))
    (tmp_path / "bench" / "configs" / "share-l2.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "reference" / "share_ref.py").write_text(
        SHARE_REFERENCE)
    (tmp_path / "bench" / "cells" / "share-l2.pack4k.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    b = bench()
    b["configs"].append({"name": "share-l2", "source": "x",
                         "file": "bench/configs/share-l2.json",
                         "reduced": cfg["reduced"], "why": "x"})
    b["workloads"].append({"name": "share-l2.pack4k", "config": "share-l2",
                           "traffic": "pack4k", "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.Cell.resolve("share-l2.pack4k", tmp_path)
    got = weights.leaves(cell.cfg)
    shape = {lf.path: lf.shape for lf in got}
    D, Fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    assert shape[("layers", "w_router")] == (1, D, 16)
    assert shape[("layers", "we13")] == (1, 4, D, 2, Fe)
    assert shape[("layers", "we2")] == (1, 4, Fe, D)
    assert got[-1].path == ("layers", "e_score_correction_bias")
    assert got[-1].shape == (1, 16)
    # every default leaf keeps its index, so its fold_in draw
    default = weights.default_leaves(cell.cfg)
    assert got[:-1] == default
    plain = weights.leaves(harness.Cell.resolve("dsv2lite-train-l2.pack4k",
                                                tmp_path).cfg)
    assert [lf.path for lf in plain] == [lf.path for lf in default]
    # the tree draws: the bias is the last leaf, drawn from its own index
    tree = jax.eval_shape(lambda k: weights.make_tree(cell.cfg, k),
                          weights.seed_key(2 ** 40 + 3))
    assert tree["layers"]["e_score_correction_bias"].shape == (1, 16)
    bias = ("layers", "e_score_correction_bias")
    assert weights.leaf_index(cell.cfg, bias) == len(default)
    # the same files under the repository's root are not there to be found
    with pytest.raises(FileNotFoundError):
        weights.leaves(dict(cell.cfg, **{harness.ROOT_KEY: str(ROOT)}))


def test_share_draws_keep_the_default_leaves_values(tmp_path):
    """At a tiny size, every leaf the share tree has in common with the
    whole tree is bitwise the same draw."""
    import jax
    import numpy as np

    (tmp_path / "bench" / "reference").mkdir(parents=True)
    (tmp_path / "bench" / "reference" / "share_ref.py").write_text(
        SHARE_REFERENCE)
    whole = harness.load_json(ROOT / "bench" / "configs" /
                              "dsv2lite-serve-l5.json")
    bench_tiny.shrink_cfg(whole)
    share = share_cfg(json.loads(json.dumps(whole)))
    share[harness.ROOT_KEY] = str(tmp_path)
    key = weights.seed_key(2 ** 40 + 3)
    a = jax.jit(lambda k: weights.make_tree(whole, k))(key)
    b = jax.jit(lambda k: weights.make_tree(share, k))(key)
    same = 0
    for lf in weights.leaves(whole):
        x, y = a, b
        for p in lf.path:
            x, y = x[p], y[p]
        if x.shape == y.shape:
            assert np.array_equal(np.asarray(x), np.asarray(y)), lf.path
            same += 1
    assert same == len(weights.leaves(whole)) - 3    # router, we13, we2
    assert b["layers"]["w_router"].shape == (4, 256, 16)
    assert b["layers"]["e_score_correction_bias"].shape == (4, 16)


# weights.leaves of the two configurations as the parent commit drew them:
# (path, shape, live shape where it differs, dtype, std)
BF, F32, STD, OUT = "bfloat16", "float32", 0.02, 0.003849001794597505
PINNED_LEAVES = {
    "dsv2lite-train-l2": [
        ("embed", (12800, 2048), None, BF, STD),
        ("final_norm_s", (2048,), None, F32, 0.0),
        ("lm_head", (2048, 12800), None, BF, STD),
        ("dense_layers.ln1_s", (1, 2048), None, F32, 0.0),
        ("dense_layers.ln2_s", (1, 2048), None, F32, 0.0),
        ("dense_layers.wq", (1, 2048, 2048), None, BF, STD),
        ("dense_layers.wk", (1, 2048, 2048), None, BF, STD),
        ("dense_layers.wv", (1, 2048, 2048), None, BF, STD),
        ("dense_layers.wo", (1, 2048, 2048), None, BF, OUT),
        ("dense_layers.w13", (1, 2048, 2, 11008), (1, 2048, 2, 10944), BF,
         STD),
        ("dense_layers.w2", (1, 11008, 2048), (1, 10944, 2048), BF, OUT),
        ("layers.ln1_s", (1, 2048), None, F32, 0.0),
        ("layers.ln2_s", (1, 2048), None, F32, 0.0),
        ("layers.wq", (1, 2048, 2048), None, BF, STD),
        ("layers.wk", (1, 2048, 2048), None, BF, STD),
        ("layers.wv", (1, 2048, 2048), None, BF, STD),
        ("layers.wo", (1, 2048, 2048), None, BF, OUT),
        ("layers.w_router", (1, 2048, 64), None, F32, STD),
        ("layers.we13", (1, 64, 2048, 2, 1408), None, BF, STD),
        ("layers.we2", (1, 64, 1408, 2048), None, BF, OUT),
        ("layers.ws13", (1, 2048, 2, 2816), None, BF, STD),
        ("layers.ws2", (1, 2816, 2048), None, BF, OUT),
    ],
    "dsv2lite-serve-l5": [
        ("embed", (12800, 2048), None, BF, STD),
        ("final_norm_s", (2048,), None, F32, 0.0),
        ("lm_head", (2048, 12800), None, BF, STD),
        ("dense_layers.ln1_s", (1, 2048), None, F32, 0.0),
        ("dense_layers.ln2_s", (1, 2048), None, F32, 0.0),
        ("dense_layers.wq", (1, 2048, 2048), None, BF, STD),
        ("dense_layers.wk", (1, 2048, 2048), None, BF, STD),
        ("dense_layers.wv", (1, 2048, 2048), None, BF, STD),
        ("dense_layers.wo", (1, 2048, 2048), None, BF, OUT),
        ("dense_layers.w13", (1, 2048, 2, 11008), (1, 2048, 2, 10944), BF,
         STD),
        ("dense_layers.w2", (1, 11008, 2048), (1, 10944, 2048), BF, OUT),
        ("layers.ln1_s", (4, 2048), None, F32, 0.0),
        ("layers.ln2_s", (4, 2048), None, F32, 0.0),
        ("layers.wq", (4, 2048, 2048), None, BF, STD),
        ("layers.wk", (4, 2048, 2048), None, BF, STD),
        ("layers.wv", (4, 2048, 2048), None, BF, STD),
        ("layers.wo", (4, 2048, 2048), None, BF, OUT),
        ("layers.w_router", (4, 2048, 64), None, F32, STD),
        ("layers.we13", (4, 64, 2048, 2, 1408), None, BF, STD),
        ("layers.we2", (4, 64, 1408, 2048), None, BF, OUT),
        ("layers.ws13", (4, 2048, 2, 2816), None, BF, STD),
        ("layers.ws2", (4, 2816, 2048), None, BF, OUT),
    ],
}
# sha256 (first 16 hex digits) of each leaf of make_tree at the
# bench_tiny size, seed 2**40 + 3, as the parent commit drew it
PINNED_DIGESTS = {
    "dsv2lite-train-l2": {
        "embed": "f327042d8fadd811",
        "final_norm_s": "5f70bf18a0860070",
        "lm_head": "2ff5982ed34dae4a",
        "dense_layers.ln1_s": "5f70bf18a0860070",
        "dense_layers.ln2_s": "5f70bf18a0860070",
        "dense_layers.wq": "e30228a3912772eb",
        "dense_layers.wk": "73a44821b7e9d0f6",
        "dense_layers.wv": "2e20c9c94ee8b1a2",
        "dense_layers.wo": "c7cb8aa3667aaa20",
        "dense_layers.w13": "486c7d56c4fe6752",
        "dense_layers.w2": "87dc33b9e2d6e0ce",
        "layers.ln1_s": "5f70bf18a0860070",
        "layers.ln2_s": "5f70bf18a0860070",
        "layers.wq": "e8cde18577170cb8",
        "layers.wk": "19e505ba218995f1",
        "layers.wv": "748dd549002f0099",
        "layers.wo": "ce0925341d2aa6ea",
        "layers.w_router": "d3924baa1fc6ba75",
        "layers.we13": "a0a0a2d48e900e4f",
        "layers.we2": "a7e8393b50da9e47",
        "layers.ws13": "22a63c58f5d90956",
        "layers.ws2": "79c6cd990095b81a",
    },
    "dsv2lite-serve-l5": {
        "embed": "f327042d8fadd811",
        "final_norm_s": "5f70bf18a0860070",
        "lm_head": "2ff5982ed34dae4a",
        "dense_layers.ln1_s": "5f70bf18a0860070",
        "dense_layers.ln2_s": "5f70bf18a0860070",
        "dense_layers.wq": "e30228a3912772eb",
        "dense_layers.wk": "73a44821b7e9d0f6",
        "dense_layers.wv": "2e20c9c94ee8b1a2",
        "dense_layers.wo": "c7cb8aa3667aaa20",
        "dense_layers.w13": "486c7d56c4fe6752",
        "dense_layers.w2": "87dc33b9e2d6e0ce",
        "layers.ln1_s": "ad7facb2586fc6e9",
        "layers.ln2_s": "ad7facb2586fc6e9",
        "layers.wq": "938f927544a4c612",
        "layers.wk": "63e0c76baf243448",
        "layers.wv": "48c9ed99822cf8a1",
        "layers.wo": "ddc312304c66d10a",
        "layers.w_router": "415a2d6b2860e375",
        "layers.we13": "b8c9e56d94f6d984",
        "layers.we2": "2b88b70616173388",
        "layers.ws13": "896f7649a03e043a",
        "layers.ws2": "82c432b807f135a4",
    },
}

@pytest.mark.parametrize("name", sorted(PINNED_LEAVES))
def test_existing_configs_keep_their_leaves(name):
    cell = next(w["name"] for w in bench()["workloads"] if w["config"] == name)
    cfg = harness.Cell.resolve(cell, ROOT).cfg
    got = [(".".join(lf.path), lf.shape,
            None if lf.live == lf.shape else lf.live, lf.dtype, lf.std)
           for lf in weights.leaves(cfg)]
    assert got == PINNED_LEAVES[name]
    assert not hasattr(harness.reference(ROOT, cfg), "leaves")
    # unpadded, every leaf is drawn and held at its live shape
    assert [lf.shape for lf in weights.leaves(cfg, padded=False)] == \
        [lf.live for lf in weights.leaves(cfg)]


@pytest.mark.parametrize("name", sorted(PINNED_DIGESTS))
def test_existing_configs_draw_bitwise_the_same_tree(name):
    import hashlib

    import jax
    import numpy as np

    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{name}.json")
    bench_tiny.shrink_cfg(cfg)
    tree = jax.jit(lambda k: weights.make_tree(cfg, k))(
        weights.seed_key(2 ** 40 + 3))
    got = {}
    for lf in weights.leaves(cfg):
        x = tree
        for p in lf.path:
            x = x[p]
        got[".".join(lf.path)] = hashlib.sha256(
            np.asarray(x).tobytes()).hexdigest()[:16]
    assert got == PINNED_DIGESTS[name]
