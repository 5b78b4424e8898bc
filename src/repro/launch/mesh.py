"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis carries
pure data parallelism (gradient all-reduce crosses DCN/pod links only once
per step).

make_production_mesh is a FUNCTION so importing this module never touches
jax device state (smoke tests must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh(model_axis: int | None = None):
    """Degenerate mesh over however many real devices exist (tests/examples)."""
    n = len(jax.devices())
    m = model_axis or 1
    assert n % m == 0
    return jax.make_mesh((n // m, m), ("data", "model"))
