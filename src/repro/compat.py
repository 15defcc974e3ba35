"""Adapters over the installed jax (0.9) where a call site needs one.

Everything else calls jax directly (``jax.shard_map``,
``jax.sharding.get_abstract_mesh``/``set_mesh``/``AbstractMesh``,
``jax.tree.flatten_with_path``).  What is left here is behaviour jax
does not offer under a public name:

* ``pvary``           — ``jax.lax.pcast(..., to="varying")`` that skips
  leaves already varying over the requested axes (the raw call rejects
  them).
* ``all_gather_inv``  — ``all_gather_invariant``, which jax 0.9 keeps
  under ``jax._src``.
* ``make_mesh``       — ``jax.make_mesh`` with Auto axes by default
  (jax 0.9 defaults to Explicit axes).
* ``manual_axis_names`` — the axes a ``shard_map`` body has bound
  manually (from the core axis env).
"""
from __future__ import annotations

import jax
from jax._src.lax.parallel import all_gather_invariant as all_gather_inv
from jax.sharding import AxisType

__all__ = ["pvary", "all_gather_inv", "make_mesh", "manual_axis_names"]


def pvary(x, axis_name):
    """Mark ``x`` varying over ``axis_name``, tolerating leaves that
    already vary.

    Warm-started block iterates are built from psum outputs, so parts
    of a while_loop carry can already vary over the mesh axes; the raw
    cast rejects that.  Per leaf, only the axes missing from the aval's
    vma set are added.
    """
    axes = tuple(axis_name) if isinstance(axis_name, (tuple, list)) \
        else (axis_name,)

    def _one(v):
        vma = getattr(getattr(v, "aval", None), "vma", None) or ()
        missing = tuple(a for a in axes if a not in vma)
        return jax.lax.pcast(v, missing, to="varying") if missing else v

    return jax.tree_util.tree_map(_one, x)


def make_mesh(shape, axes, axis_types=None, *, devices=None):
    """``jax.make_mesh`` with Auto axes unless ``axis_types`` says
    otherwise."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axes)
    return jax.make_mesh(shape, axes, axis_types=axis_types,
                         devices=devices)


def manual_axis_names() -> set:
    """Mesh axes bound manually at trace time (inside a shard_map body),
    so sharding constraints never name an axis that shard_map already
    made manual."""
    try:
        from jax._src.core import get_axis_env
        return set(getattr(get_axis_env(), "axis_sizes", {}).keys())
    except Exception:
        return set()
