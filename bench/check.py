"""The comparison that decides ``correct``.

Each timed solve returns ``(U, S, V)``.  Once the window has closed, a
sample of the solves drawn from the seed is held to what the leading
``k`` singular triplets of ``A`` are, with ``A`` applied by the plain
reference's products (``RefOps``, ``Precision.HIGHEST``) and the small
products in float64 on the host:

``resid_left``   ``max_i ||A v_i - S_i u_i|| / S_i``;
``resid_right``  ``max_i ||A^T u_i - S_i v_i|| / S_i``;
``orth_err``     ``max(|U^T U - I|, |V^T V - I|)``, entrywise;
``sigma_err``    ``max_i |S_i - s_i| / s_i`` against the planted leading
                 ``k`` singular values ``s``, in descending order.

``resid_left`` covers the Rayleigh-Ritz extraction (and, with a mesh,
the row-sharded ``U``); ``resid_right`` covers the iterate, since only
a converged right subspace gives a small right residual (so the sweep,
the streamed blocks and the psum'd chain); ``orth_err`` covers the QR
of the iterate and of ``W = A Q``; ``sigma_err`` pins the triplets to
the leading ``k`` in order (exact triplets of the tail, or in another
order, pass the other three).  The residuals of the whole sample are
read with one product each.  A number is correct when it is finite and
at most its limit; a run is correct when every number is, and no solve
failed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: the numbers, in the order they are printed
NAMES = ("resid_left", "resid_right", "orth_err", "sigma_err")


def _orth_err(X) -> float:
    X = np.asarray(X, np.float64)
    return float(np.max(np.abs(X.T @ X - np.eye(X.shape[1]))))


@jax.jit
def _resid(AV, AtU, U, S, V):
    left = jnp.linalg.norm(AV - U * S, axis=0) / S
    right = jnp.linalg.norm(AtU - V * S, axis=0) / S
    return jnp.max(left), jnp.max(right)


def compare(ops, s, sample) -> dict:
    """The numbers for the sampled solves' ``(U, S, V)``; ``ops`` is the
    reference's ``RefOps`` on ``A``, ``s`` the planted leading ``k``
    singular values in float64."""
    U = jnp.concatenate([u for u, _, _ in sample], axis=1)
    S = jnp.concatenate([jnp.asarray(s) for _, s, _ in sample])
    V = jnp.concatenate([v for _, _, v in sample], axis=1)
    left, right = _resid(ops.mm(V), ops.rmm(U), U, S, V)
    return {"resid_left": float(left), "resid_right": float(right),
            "orth_err": max(max(_orth_err(u), _orth_err(v))
                            for u, _, v in sample),
            "sigma_err": max(float(np.max(np.abs(
                np.asarray(S_i, np.float64) - s) / s))
                for _, S_i, _ in sample)}


def verdict(numbers: dict, limits: dict) -> dict:
    """``{name: {"value": v, "limit": l, "ok": bool}}`` for every limit."""
    out = {}
    for name, limit in limits.items():
        v = numbers.get(name, float("nan"))
        out[name] = {"value": v, "limit": limit,
                     "ok": bool(np.isfinite(v) and v <= limit)}
    return out
