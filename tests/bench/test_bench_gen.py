"""The traffic generator: seeded, within its clips, the same work for
every seed."""
import json
from collections import Counter

import numpy as np
import pytest

import bench_tiny
from bench import gen

BIG = 2 ** 31 + 977          # seeds wider than 32 signed bits


def mix(name):
    with open(f"{bench_tiny.ROOT}/bench/traffic/{name}.json") as f:
        return json.load(f)


def test_packed_rows_are_seeded_and_packed():
    m = mix("pack4k")
    a, b = gen.PackedRows(m, 12800, BIG), gen.PackedRows(m, 12800, BIG)
    x, y = a.batch(3), b.batch(3)
    for k in x:
        np.testing.assert_array_equal(x[k], y[k])
    assert x["tokens"].shape == (2, 4096) and x["tokens"].dtype == np.int32
    np.testing.assert_array_equal(x["tokens"][:, 1:], x["targets"][:, :-1])
    assert x["tokens"].min() >= 0 and x["tokens"].max() < 12800
    assert (x["tokens"] == m["eos_id"]).sum() >= 1        # documents packed
    assert not np.array_equal(a.batch(4)["tokens"], x["tokens"])
    assert not np.array_equal(gen.PackedRows(m, 12800, 1).batch(3)["tokens"],
                              x["tokens"])


def test_zipf_ids_are_skewed():
    s = gen.IdSampler({"a": 1.1, "lo": 1}, 12800, 5)
    ids = s(np.random.default_rng(0), 200_000)
    assert ids.min() >= 1 and ids.max() < 12800
    top = Counter(ids.tolist()).most_common(1)[0][1] / len(ids)
    assert 0.05 < top < 0.2             # rank 1 of Zipf(1.1) over 12.8k ids


def test_uniform_ids_are_flat():
    s = gen.IdSampler({"dist": "uniform", "lo": 1}, 12800, 5)
    ids = s(np.random.default_rng(0), 200_000)
    assert ids.min() >= 1 and ids.max() < 12800
    top = Counter(ids.tolist()).most_common(1)[0][1] / len(ids)
    assert top < 1e-3                   # 1/12799 = 7.8e-5 a id on average
    with pytest.raises(ValueError):
        gen.IdSampler({"dist": "bimodal"}, 12800, 5)


@pytest.mark.parametrize("name", ["chat", "longdoc"])
def test_requests_seeded_clipped_same_work(name):
    m = mix(name)
    r1 = gen.requests(m, 4.0, 30.0, 12800, BIG)
    r2 = gen.requests(m, 4.0, 30.0, 12800, BIG)
    assert [(r.due, r.prompt, r.max_new) for r in r1] == \
        [(r.due, r.prompt, r.max_new) for r in r2]
    assert all(r.due < 30.0 for r in r1)
    assert 100 <= len(r1) <= 120
    pl, ol = m["prompt_len"], m["output_len"]
    assert all(pl["min"] <= len(r.prompt) <= pl["max"] for r in r1)
    assert all(ol["min"] <= r.max_new <= ol["max"] for r in r1)
    assert all(len(r.prompt) + r.max_new <= m["engine"]["max_len"] for r in r1)
    # another seed: other order and ids, the same multiset of sizes
    r3 = gen.requests(m, 4.0, 30.0, 12800, 3)
    full = gen.request_count(4.0, 30.0)
    a = gen.requests(m, 4.0, 30.0, 12800, BIG, cut=False)
    b = gen.requests(m, 4.0, 30.0, 12800, 3, cut=False)
    assert len(a) == len(b) == full
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in r3] != [len(r.prompt) for r in r1]


def test_lognormal_quantiles_median():
    v = gen.lognormal_quantiles({"median": 512, "sigma": 0.8, "min": 32,
                                 "max": 2048}, 101)
    assert v[50] == 512 and v.min() >= 32 and v.max() <= 2048


def test_poisson_gaps_mean():
    g = gen.gaps({"process": "poisson"}, 4.0, 1000)
    assert g.mean() == pytest.approx(0.25, rel=0.01)


def test_engine_shapes():
    assert gen.engine_shapes(mix("chat")) == [
        (128, False), (128, True), (256, False), (256, True), (512, False),
        (512, True)]
    assert gen.engine_shapes(mix("longdoc")) == [
        (128, True), (256, True), (512, False), (512, True)]
