"""Set-up seconds: process start (the first line of bench/run.py) to the
opening of the measured window -- JAX start-up, weights, compile or cache
loads, warm-up and the first checked steps."""
from __future__ import annotations

LAYER = "end_to_end"
UNIT = "s"
BETTER = "lower"
MOVES = None
SOURCE = "host_clock"


def read(run):
    return run.record["setup_s"]
