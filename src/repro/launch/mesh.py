"""Production meshes.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis is pure data parallelism across the inter-pod (DCN-ish) links;
weights are replicated per pod, gradients all-reduce over it.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before anything initialises a
backend).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many (host) devices tests have."""
    return _make_mesh(shape, axes)
