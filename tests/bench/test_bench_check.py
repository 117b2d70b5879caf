"""The comparison that decides ``correct``, at a tiny size on the CPU: it
passes the program where the program is sound, fails the int4 control,
and catches the FP8-flow expert weight-gradient shortfall."""
import jax
import numpy as np
import pytest

import bench_tiny
from bench import check, gen, harness, serve_cell, train_cell, weights

SEED = 3


def tiny(edit, name="dsv2lite-train-l2.pack4k"):
    cell = harness.Cell.resolve(name)
    edit(cell)
    return cell


def program(cell, seed=SEED):
    b = train_cell.build(cell)
    key = weights.seed_key(seed)
    rows = gen.PackedRows(cell.mix, cell.cfg["vocab_size"], seed)
    with jax.set_mesh(b.mesh):
        st = b.init(key)
        _, prog = train_cell.program_first_steps(b, st, rows, key)
    return prog


@pytest.fixture(scope="module")
def bf16():
    cell = tiny(bench_tiny.shrink_bf16)
    return cell, program(cell), train_cell.reference_readings(cell, SEED)


def test_bf16_program_agrees_with_reference(bf16):
    cell, prog, ref = bf16
    ok, compared = check.judge(check.train_numbers(prog, ref),
                               bench_tiny.TINY_TRAIN_LIMITS)
    assert ok, compared


def test_int4_control_fails(bf16):
    cell, _, ref = bf16
    ctrl = train_cell.reference_readings(cell, SEED, quant="int4")
    ok, compared = check.judge(check.train_numbers(ctrl, ref),
                               bench_tiny.TINY_TRAIN_LIMITS)
    assert not ok, compared


def test_fp8_flow_expert_wgrad_shortfall_is_caught():
    """Under fp8_flow the routed experts' weight gradients come out short:
    the scaling-aware transpose rebases a 128-row block of a padded expert
    buffer onto its largest row scale, and an all-zero padding row has scale
    1, so the live rows' small gradients flush to zero.  The comparison
    must see it, on those leaves."""
    cell = tiny(bench_tiny.shrink)
    prog = program(cell)
    ref = train_cell.reference_readings(cell, SEED)
    nums = check.train_numbers(prog, ref)
    ok, _ = check.judge(nums, bench_tiny.TINY_TRAIN_LIMITS)
    assert not ok and nums["grad_gap"] > 0.05
    assert nums["loss_gap"] < bench_tiny.TINY_TRAIN_LIMITS["loss_gap"]
    worst = check.worst_leaves(prog, ref, 2)
    assert {w.split(":")[0] for w in worst} <= {"layers.we13", "layers.we2"}


def test_served_tokens_against_reference_and_control():
    """Requests served to the end (all submitted at once, so the batches are
    the same every run): the served tokens' widest gap is small, the int4
    control's is over three times as wide, and a reference whose routed
    experts give nothing reads over the limit too: the comparison sees the
    routed experts."""
    from repro.serve.scheduler import Request

    cell = tiny(bench_tiny.shrink, "dsv2lite-serve-l5.chat")
    eng = serve_cell.build_engine(cell, SEED)
    reqs = gen.requests(cell.mix, 6.0, 1.5, 512, SEED)
    res = eng.run([Request(prompt=r.prompt, max_new_tokens=r.max_new,
                           rid=r.index) for r in reqs], realtime=False)
    items = [(r.prompt, res[r.index]["tokens"]) for r in reqs]
    gaps, others = serve_cell.reference_gaps(cell, SEED, items,
                                             ["int4", "no_experts"])
    g = max(float(np.max(x)) for x in gaps)
    c = max(float(np.max(x)) for x in others["int4"])
    f = max(float(np.max(x)) for x in others["no_experts"])
    assert sum(len(x) for x in gaps) >= 30
    assert g < 0.03 < c and c > 3 * g, (g, c)
    assert f > 0.03, f
