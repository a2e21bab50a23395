"""Production mesh factories.

Functions, not module-level constants, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) -- ``pod`` is
an outer data-parallel ring (gradient all-reduce crosses the inter-pod
links; everything else stays inside a pod).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 0, model: int = 2):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = n_devices or len(jax.devices())
    model = min(model, n)
    return make_mesh((n // model, model), ("data", "model"))
