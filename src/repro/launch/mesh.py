"""Mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names, devices=None):
    """A mesh whose axes are all ``Auto``: shardings inside the program are
    left to XLA's propagation (jax.make_mesh defaults to ``Explicit``)."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment mesh of the CPU lowering tools (launch/dryrun.py,
    launch/sweep.py).  Single pod: (data=16, model=16) = 256 chips (TPU v5e
    pod slice).  Multi-pod: a leading pod=2 axis = 512 chips; the pod axis
    carries pure data parallelism (gradient all-reduce over the slow
    inter-pod links)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_device_mesh(model: int | None = None):
    """(data, model) mesh over the devices present: ``model`` chips per
    model-parallel group (default: all of them), the rest data parallel."""
    n = jax.device_count()
    model = n if model is None else model
    if n % model:
        raise ValueError(f"model={model} does not divide {n} devices")
    return make_mesh((n // model, model), ("data", "model"))


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for single-process tests."""
    return make_mesh(shape, axes)
