"""Pallas TPU kernels for the FP8 pathway; ``ops`` holds the jitted
wrappers and ``ref`` their pure-jnp oracles.

Interpret mode follows the backend alone: the kernels run in the
TPU-semantics Pallas interpreter on the CPU backend (tests) and are compiled
everywhere else.  A kernel asked to interpret on a TPU raises instead of
silently running slow.
"""
from __future__ import annotations

import jax


def interpret_mode():
    """The ``interpret`` argument of this backend's kernels: on the CPU the
    TPU-semantics Pallas interpreter (which, unlike the plain HLO one, runs
    inside ``shard_map``), on every other backend False (compiled)."""
    if jax.default_backend() == "cpu":
        from jax.experimental.pallas import tpu as pltpu
        return pltpu.InterpretParams()
    return False


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """A kernel output's type: varying over every manual mesh axis that one
    of ``operands`` varies over (a ``pallas_call`` inside ``shard_map``
    needs it; elsewhere the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def checked_interpret(interpret):
    """Pass ``interpret`` through, refusing interpret mode on a TPU."""
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError("Pallas kernels are compiled on a TPU; interpret "
                           "mode is for the CPU backend only")
    return interpret
