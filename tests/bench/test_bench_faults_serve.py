"""A whole run of the tiny chat cell through the harness, past its look for
a chip: sound, it reads correct; with served tokens altered where the
engine produces them, or with a step that hands back its KV pool unwritten,
it reads not correct."""
import pytest

import bench_tiny
from bench import gen, harness, serve_cell


def edit(cell):
    bench_tiny.shrink(cell)
    # tiny readings (CPU): 0.013 sound, 0.051 for the int4 control, 1.4
    # with tokens altered
    cell.cell["limits"] = {"gap": 0.03}


@pytest.mark.parametrize("fault,want", [(None, True), ("alter_token", False),
                                        ("stale_pool", False)])
def test_serve_run(fault, want):
    out = harness.run_cell("dsv2lite-serve-l5.chat", 2 ** 33 + 1, 3.0, False,
                           require_chip=False, peaks=bench_tiny.PEAKS,
                           fault=fault, edit=edit)
    assert out["correct"] is want, out["compared"]
    assert out["attempted"] >= 10
    assert set(out["metrics"]) == {"ttft_p90_ms", "tbt_p95_ms", "setup_s"}
    assert out["compared"]["gap"]["limit"] == 0.03


def test_warm_readies_every_step_shape_through_tick():
    """Set-up runs each (prefill bucket, decode, history) step the mix can
    make, and the decode-only step, through ``ServeEngine.tick``, leaves
    the engine empty, and leaves nothing to compile: a second warm-up, on
    the pool as the steps hand it on, adds no program."""
    cell = harness.Cell.resolve("dsv2lite-serve-l5.chat")
    bench_tiny.shrink(cell)
    eng = serve_cell.build_engine(cell, 11)
    vocab = cell.cfg["vocab_size"]
    shapes = serve_cell.warm(eng, cell.mix, vocab)
    want = {(b, d, h) for b, h in gen.engine_shapes(cell.mix)
            for d in (False, True)} | {(None, True, False)}
    assert set(shapes) == want
    assert not eng.sched.active and not eng.sched.waiting
    n = eng._step_fn._cache_size()
    assert n == len(want) + 1        # the first step also ran on the new pool
    serve_cell.warm(eng, cell.mix, vocab)
    assert eng._step_fn._cache_size() == n
