"""Production mesh construction.

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

Mesh shapes:
  single-pod: (data=16, model=16)        -- 256 chips (one v5e pod)
  multi-pod : (pod=2, data=16, model=16) -- 512 chips across DCI

The ``pod`` axis composes with ``data`` for hierarchical data parallelism
(gradient reduce-scatter crosses ICI first, then DCI) and is the pipeline
axis when pipeline parallelism is enabled.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (smoke tests use (1, 1) or (1, 2) CPU meshes).

    Axes are ``Auto``: ``jax.make_mesh`` defaults to ``Explicit`` axes,
    under which ``with_sharding_constraint`` and the sharding rules'
    gathers raise.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


def batch_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch (pure-DP axes)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes used for parameter (FSDP/ZeRO) sharding."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
