"""Truncated-SVD (power-method) gradient compression with error feedback.

The paper's distributed power method, applied as a distributed-optimization
trick: before gradients cross the scarce inter-pod links, each 2-D
parameter's gradient matrix ``M (p x q)`` is factored to rank ``r`` with
one block power-iteration step (the paper's Alg 2 run on ``M`` with a warm-
started subspace — the block variant is the paper's own reference [2],
Bentbib & Kanber), and only the skinny factors ``P (p x r)`` and
``Q (q x r)`` are all-reduced:

    P = M @ Q_prev            -> all-reduce, orthonormalize
    Q = M^T @ P               -> all-reduce
    M_hat = P @ Q^T;  error <- M - M_hat   (fed back next step)

Per-step cross-pod bytes drop from ``p*q`` to ``r*(p+q)`` — for a 4096x4096
layer at r=8 that is 256x less DCI traffic.  Error feedback keeps the
optimizer unbiased in the long run (PowerSGD lineage, arXiv:1905.13727 —
itself a one-step power method, i.e. exactly the paper's kernel).

Non-matrix leaves (norm scales, biases) and leaves below ``min_size`` are
all-reduced uncompressed.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


PyTree = Any


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    rank: int = 8
    min_size: int = 65_536      # don't compress small leaves
    seed: int = 17
    enabled: bool = True


def _mat_shape(shape: tuple[int, ...]) -> tuple[int, int] | None:
    """Collapse an nD weight to 2D (leading dims x last dim); None = skip."""
    if len(shape) < 2:
        return None
    p = 1
    for d in shape[:-1]:
        p *= d
    return p, shape[-1]


def init_state(params: PyTree, cfg: CompressionConfig) -> PyTree:
    """Warm-start Q subspaces + error buffers per compressible leaf."""
    flat, treedef = jax.tree.flatten_with_path(params)
    qs, errs = [], []
    for i, (path, p) in enumerate(flat):
        ms = _mat_shape(p.shape)
        if not cfg.enabled or ms is None or p.size < cfg.min_size:
            qs.append(())
            errs.append(())
            continue
        _, q = ms
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), i)
        Q = jax.random.normal(key, (q, cfg.rank), jnp.float32)
        Q, _ = jnp.linalg.qr(Q)
        qs.append(Q)
        errs.append(jnp.zeros(p.shape, jnp.float32))
    unflatten = lambda leaves: jax.tree.unflatten(treedef, leaves)
    return {"Q": unflatten(qs), "err": unflatten(errs)}


def _orthonormalize(P: jax.Array) -> jax.Array:
    """QR-based orthonormalization (r is small; cost r^2 p)."""
    Q, _ = jnp.linalg.qr(P.astype(jnp.float32))
    return Q


def compress_grads(grads: PyTree, state: PyTree, cfg: CompressionConfig,
                   axis_name: str | None = None):
    """Compress+decompress gradients with error feedback.

    ``axis_name`` — mesh axis to mean-reduce across (the pod axis).  When
    None (single-pod training or unit tests) the math runs identically
    with no collective, so tests validate the exact deployed computation.

    Returns (decompressed_grads, new_state, stats).
    """
    pmean = (lambda x: jax.lax.pmean(x, axis_name)) if axis_name else (
        lambda x: x)

    flat_g, treedef = jax.tree.flatten(grads)
    flat_q = jax.tree.leaves(state["Q"],
                             is_leaf=lambda x: isinstance(x, tuple) or hasattr(x, "shape"))
    flat_e = jax.tree.leaves(state["err"],
                             is_leaf=lambda x: isinstance(x, tuple) or hasattr(x, "shape"))

    out_g, out_q, out_e = [], [], []
    bytes_full = 0
    bytes_sent = 0
    for g, Q, e in zip(flat_g, flat_q, flat_e):
        if isinstance(Q, tuple):  # not compressed: plain all-reduce
            out_g.append(pmean(g))
            out_q.append(())
            out_e.append(())
            bytes_full += g.size * 4
            bytes_sent += g.size * 4
            continue
        shape = g.shape
        M = g.astype(jnp.float32).reshape(_mat_shape(shape)) + e.reshape(
            _mat_shape(shape))
        P = pmean(M @ Q)                     # (p, r)   cross-pod bytes: p*r
        P = _orthonormalize(P)
        Qn = pmean(M.T @ P)                  # (q, r)   cross-pod bytes: q*r
        M_hat = P @ Qn.T
        err_new = (M - M_hat).reshape(shape)
        out_g.append(M_hat.reshape(shape).astype(g.dtype))
        out_q.append(_orthonormalize(Qn))    # warm start for next step
        out_e.append(err_new)
        bytes_full += M.size * 4
        bytes_sent += (P.size + Qn.size) * 4

    new_state = {
        "Q": jax.tree.unflatten(treedef, out_q),
        "err": jax.tree.unflatten(treedef, out_e),
    }
    stats = {
        "compress_ratio": jnp.asarray(
            bytes_full / max(bytes_sent, 1), jnp.float32),
    }
    return jax.tree.unflatten(treedef, out_g), new_state, stats
