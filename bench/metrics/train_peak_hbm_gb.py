"""Peak device memory of the training process (GB = 1e9 bytes): the
fullest chip's peak_bytes_in_use, read after the window and before the
reference runs."""
from __future__ import annotations

LAYER = "end_to_end"
UNIT = "GB"
BETTER = "lower"
MOVES = None
SOURCE = "host_clock"


def read(run):
    b = run.record["peak_bytes"]
    return None if b is None else b / 1e9
