"""The trace reduction on small synthetic event lists."""
import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import tracefold as tf
from bench.tracefold import Op, Span

EXPERT = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/" \
    "rematted_computation/stage/expert/dot_general"
ATTN_FWD = "jit(train_step)/jvp()/while/body/closed_call/stage/attn/dot_general"
ATTN_BWD = "jit(train_step)/transpose(jvp(stage))/attn/mul"


def ops():
    return [Op("fusion.1", ATTN_FWD, 1.0, 0.5),      # 1.0 - 1.5
            Op("fusion.2", EXPERT, 1.25, 0.5),       # overlaps: 1.25 - 1.75
            Op("convolution.3", EXPERT, 2.0, 1.0),   # 2.0 - 3.0
            Op("copy.4", "jit(step)/copy", 3.5, 0.25),
            Op("fusion.5", ATTN_BWD, 9.0, 1.0)]      # outside the window


def spans():
    return [Span(tf.WINDOW_SPAN, 1.0, 3.0),          # 1.0 - 4.0
            Span("bench/step", 1.0, 0.8),
            Span("bench/feed", 1.7, 0.4),
            Span("bench/step", 2.2, 1.8)]


def test_union_counts_overlap_once():
    assert tf.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    # 1.0-1.75 (0.75) + 2.0-3.0 (1.0) + 3.5-3.75 (0.25)
    assert tf.busy_seconds(ops(), 1.0, 4.0) == pytest.approx(2.0)


def test_clip_cuts_to_window():
    c = tf.clip(ops(), 1.5, 2.5)
    assert [(o.name, o.start, o.dur) for o in c] == [
        ("fusion.2", 1.5, pytest.approx(0.25)),
        ("convolution.3", 2.0, pytest.approx(0.5))]


@pytest.mark.parametrize("scope,path,want", [
    ("stage/expert", EXPERT, True),
    ("stage/attn", ATTN_FWD, True),
    ("stage/attn", ATTN_BWD, True),
    ("stage/attn", EXPERT, False),
    ("stage/expert", "jit(f)/stage/experts/dot", False),
    ("stage/expert", "", False),
])
def test_scope_matching(scope, path, want):
    assert tf.has_scope(Op("x", path, 0.0, 1.0), scope) is want


def test_scope_seconds_keep_overlap_per_op():
    assert tf.scope_seconds(ops(), "stage/expert", 1.0, 4.0) == \
        pytest.approx(1.5)
    assert tf.scope_seconds(ops(), "stage/attn", 1.0, 4.0) == \
        pytest.approx(0.5)


def test_idle_gaps_are_labelled_by_host_span():
    gaps = tf.idle_gaps(ops(), spans(), 1.0, 4.0)
    # holes: 1.75-2.0 (feed covers 1.75-2.1 most), 3.0-3.5 and 3.75-4.0
    # (the second step covers both)
    assert [(n, pytest.approx(s)) for n, s in gaps] == [
        ("bench/step", 0.5), ("bench/feed", 0.25), ("bench/step", 0.25)]
    assert sum(s for _, s in gaps) + tf.busy_seconds(ops(), 1.0, 4.0) == \
        pytest.approx(3.0)


def test_gap_without_host_span():
    gaps = tf.idle_gaps([Op("a", "", 0.0, 1.0)], [], 0.0, 2.0)
    assert gaps == [("no host span", 1.0)]


def test_breakdown_and_reduce():
    red = tf.reduce({0: ops(), 1: ops()[:1]}, spans())
    assert red.window_s == pytest.approx(3.0)
    # mean of device 0 (2.0 s) and device 1 (0.5 s)
    assert red.busy_s == pytest.approx(1.25)
    top = red.breakdown["device_ops"]
    assert top[0] == ["expert:convolution", pytest.approx(1.0)]
    assert ["attn:fusion", pytest.approx(0.5)] in top
    assert red.breakdown["idle_gaps"][0][0] == "bench/step"
    assert red.scope_s("stage/expert") == pytest.approx(1.5)


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        tf.reduce({0: ops()}, spans()[1:])
    with pytest.raises(ValueError):
        tf.reduce({}, spans())


HLO = '''HloModule jit_step
%body {
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(step)/transpose(jvp(stage))/expert/mul" source_file="x.py"}
}
ENTRY %main {
  %while.2 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%body, metadata={op_name="jit(step)/stage/attn/while"}
  ROOT %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%fc.2
}
'''


def test_ops_are_named_from_the_hlo():
    assert tf.head("%while.2 = (s32[], f32[8]{0}) while(%t), body=%b") == \
        "%while.2 = (s32[], f32[8]{0})"
    assert tf.head("%fusion.3 = f32[8]{0} fusion(%p), kind=kLoop") == \
        "%fusion.3 = f32[8]{0}"
    m = tf.scope_map([HLO])
    assert m["%fusion.3 = f32[8]{0}"] == \
        "jit(step)/transpose(jvp(stage))/expert/mul"
    assert m["%while.2 = (s32[], f32[8]{0})"] == "jit(step)/stage/attn/while"
    assert m["%fusion.3 = bf16[8]{0}"] == ""          # same name, other type
    # two modules that disagree on a head drop it
    other = HLO.replace("stage/attn/while", "stage/router/while")
    assert "%while.2 = (s32[], f32[8]{0})" not in tf.scope_map([HLO, other])


def test_nested_ops_count_once():
    loop = Op("while.2", "jit(s)/stage/attn/while", 0.0, 1.0)
    body = [Op("fusion.3", "jit(s)/stage/attn/mul", 0.1, 0.3),
            Op("fusion.4", "jit(s)/stage/attn/add", 0.5, 0.4)]
    after = Op("copy.5", "", 1.0, 0.5)
    ops = [loop, *body, after]
    assert [o.name for o in tf.leaves(ops)] == ["fusion.3", "fusion.4",
                                                "copy.5"]
    assert tf.scope_seconds(ops, "stage/attn", 0.0, 2.0) == pytest.approx(1.0)
    assert tf.busy_seconds(ops, 0.0, 2.0) == pytest.approx(1.5)
    b = tf.breakdown(ops, [], 0.0, 2.0)
    assert [k for k, _ in b["device_ops"]] == ["attn:fusion", "copy"]
