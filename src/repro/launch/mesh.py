"""Production mesh construction.

A FUNCTION (not module-level state) so importing never touches jax device
initialization.  The dry-run process sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single device.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh for tests/elastic configs (device-count permitting).

    Every axis is ``Auto``: the model places data with logical-axis
    ``with_sharding_constraint`` calls (parallel/context.py), which only
    accept Auto axes (``jax.make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def serve_mesh(dp: int, tp: int):
    """The serving mesh shape: (dp, tp) over ("data", "model") — slots and
    pos tracks shard over "data", heads/KV pools over "model" (DESIGN.md
    §9).  Needs dp * tp visible devices; on a CPU-only host force them
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before*
    the first jax import (the sharded test/bench subprocesses do)."""
    if dp * tp > len(jax.devices()):
        raise ValueError(
            f"serve mesh ({dp}, {tp}) needs {dp * tp} devices, have "
            f"{len(jax.devices())}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={dp * tp} "
            f"before jax initializes (CPU), or shrink the mesh")
    return make_mesh((dp, tp), ("data", "model"))


def single_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
