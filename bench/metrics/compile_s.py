"""Backend compile seconds of the set-up, up to the window's opening
(jax.monitoring's /jax/core/compile/backend_compile_duration); a program
loaded from the persistent cache adds none."""
from __future__ import annotations

LAYER = "launch"
UNIT = "s"
BETTER = "lower"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    return run.record["compile_s"]
