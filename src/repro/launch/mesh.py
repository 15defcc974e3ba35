"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model); the leading
``pod`` axis crosses the inter-pod (DCI) links — gradient sync across it
is where the SVD gradient compression (repro.optim.compression) earns its
keep.

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / examples)."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
