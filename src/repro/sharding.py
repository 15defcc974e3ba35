"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Every parameter / activation dimension carries a *logical* name; the rules
table maps logical names onto physical mesh axes.  Changing the parallelism
layout (the §Perf hillclimb does this) means editing ONE table, not the
model code.

Physical mesh axes (see launch/mesh.py):
  * ``pod``   — slowest axis, inter-pod DCI (multi-pod runs only)
  * ``data``  — intra-pod, used for FSDP + batch data-parallelism
  * ``model`` — intra-pod, used for tensor/expert parallelism

Default layout = FSDP(data) x TP(model) x DP(pod):
  * weights:   FSDP-shard the "long" dim over ``data``, TP-shard heads/ffn
               over ``model`` (GSPMD inserts the just-in-time all-gathers)
  * activations: batch over (pod, data); ffn/heads over ``model``
  * MoE: experts kept whole, both internal dims sharded (embed->data,
    mlp->model) so expert weights never exceed one chip's HBM.
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat

# logical axis -> physical mesh axis (or tuple, or None)
DEFAULT_RULES: dict[str, object] = {
    # global batch is split across pod and data axes
    "batch": ("pod", "data"),
    "seq": None,            # sequence kept whole by default (SP off)
    "embed": None,          # activation embed dim replicated
    # parameter dims
    "vocab": "model",       # embedding/lm-head vocab dim -> TP
    "embed_p": "data",      # parameter embed dim -> FSDP
    "heads": "model",       # q heads -> TP
    "kv_heads": "model",    # kv heads -> TP (falls back below if indivisible)
    "qkv": None,            # per-head feature dim
    "mlp": "model",         # ffn hidden -> TP
    "expert": None,         # experts unsharded (internal dims are sharded)
    "rnn": "model",         # recurrent width -> TP
    "seq_shard": "model",   # context-parallel fallback (heads % tp != 0)
    "cache_seq": "model",   # decode KV cache: shard the TIME axis over TP
                            # (kv_heads rarely divide 16; 32k positions
                            #  always do — keeps grok's 1.1TB cache at
                            #  4.3GB/chip)
    "layers": None,         # stacked-scan leading dim
    "window": None,
    "codebook": None,
}

_STATE = threading.local()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Install ``mesh`` as the ambient mesh for ``constrain`` and jit."""
    with jax.sharding.set_mesh(mesh):
        yield mesh


def get_rules() -> dict[str, object]:
    return getattr(_STATE, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def rules(overrides: dict[str, object]):
    """Temporarily override logical->physical rules (used by §Perf runs)."""
    old = get_rules()
    _STATE.rules = {**old, **overrides}
    try:
        yield
    finally:
        _STATE.rules = old


def _mesh_axes(mesh: Mesh) -> set[str]:
    """Mesh axes usable in sharding constraints (excludes Manual axes —
    inside a partial-manual shard_map the manual axis is off-limits to
    with_sharding_constraint)."""
    try:
        types = dict(zip(mesh.axis_names, mesh.axis_types))
        axes = {n for n, t in types.items() if "Manual" not in str(t)}
    except Exception:
        axes = set(mesh.axis_names)
    # On jax versions without typed mesh axes, manual axes are only
    # visible through the trace-time axis env.
    return axes - compat.manual_axis_names()


def resolve_spec(logical: tuple[str | None, ...], mesh: Mesh,
                 dim_sizes: tuple[int, ...] | None = None) -> P:
    """Map a tuple of logical names to a PartitionSpec for ``mesh``.

    Drops axes the mesh doesn't have (e.g. ``pod`` on single-pod) and any
    mapping that doesn't divide the dimension (e.g. kv_heads=1 over
    model=16 falls back to replicated) — this keeps one config portable
    across meshes, which is what lets the same arch config compile on both
    the single-pod and multi-pod dry-run meshes.
    """
    table = get_rules()
    have = _mesh_axes(mesh)
    out = []
    used: set[str] = set()
    for i, name in enumerate(logical):
        if name is None:
            out.append(None)
            continue
        phys = table.get(name, None)
        if phys is None:
            out.append(None)
            continue
        axes = (phys,) if isinstance(phys, str) else tuple(phys)
        axes = tuple(a for a in axes if a in have and a not in used)
        if not axes:
            out.append(None)
            continue
        if dim_sizes is not None:
            total = 1
            for a in axes:
                total *= mesh.shape[a]
            if dim_sizes[i] % total != 0:
                out.append(None)
                continue
        used.update(axes)
        out.append(axes[0] if len(axes) == 1 else axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def named_sharding(logical: tuple[str | None, ...], mesh: Mesh,
                   dim_sizes: tuple[int, ...] | None = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(logical, mesh, dim_sizes))


def constrain(x: jax.Array, *logical: str | None) -> jax.Array:
    """Apply a logical sharding constraint using the ambient abstract mesh.

    No-op outside a mesh context (unit tests on one device).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or not mesh.axis_names:
        return x
    spec = resolve_spec(tuple(logical), mesh, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def tree_shardings(spec_tree, mesh: Mesh, shape_tree=None):
    """Map a pytree of logical-axis tuples to NamedShardings.

    ``shape_tree`` (matching pytree of shapes) enables divisibility
    fallback per leaf.
    """
    if shape_tree is None:
        return jax.tree.map(
            lambda spec: named_sharding(tuple(spec), mesh),
            spec_tree, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.map(
        lambda spec, shp: named_sharding(tuple(spec), mesh, tuple(shp)),
        spec_tree, shape_tree, is_leaf=lambda x: isinstance(x, tuple))
