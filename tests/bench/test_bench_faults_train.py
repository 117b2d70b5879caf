"""A whole run of the tiny training cell through the harness, past its look
for a chip: sound, it reads correct; with the timed path broken underneath
it reads not correct."""
import pytest

import bench_tiny
from bench import harness


@pytest.mark.parametrize("fault,want", [(None, True),
                                        ("stale_state", False),
                                        ("half_batch", False)])
def test_train_run(fault, want):
    out = harness.run_cell("dsv2lite-train-l2.pack4k", 2 ** 31 + 5, 1.0,
                           False, require_chip=False, peaks=bench_tiny.PEAKS,
                           fault=fault, edit=bench_tiny.shrink_bf16)
    assert out["correct"] is want, out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    # the CPU keeps no peak-memory counter, so train_peak_hbm_gb reads none
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(out)[-1] == "compared"
