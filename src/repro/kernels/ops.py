"""Jit'd public wrappers around the Pallas kernels.

Handles shape padding to tile boundaries, interpret mode on the CPU
backend only (``interpret=True`` executes the kernel body in Python for
correctness; any other backend compiles), and sensible tile defaults
per op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import block_matvec as _bm
from repro.kernels import gram as _gram
from repro.kernels import deflate_matvec as _dm
from repro.kernels import local_attn as _la
from repro.kernels import ref as _ref


def _interpret() -> bool:
    """Interpret mode is the CPU's stand-in for Mosaic; every other
    backend compiles the kernel (and fails loudly if it cannot)."""
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


# TPU lane width: the last dimension of every VMEM tile maps onto the
# 128-wide lane axis, so the k (column-count) dimension of the
# multi-vector RHS/output tiles must be padded to a multiple of 128 —
# Mosaic rejects arbitrary k on real TPU.  Zero columns are exact for
# every op here (they produce zero output columns, cropped on return).
_LANE = 128


def gram(A: jax.Array, *, bn: int = 256, bk: int = 512,
         symmetric: bool = True, interpret: bool | None = None) -> jax.Array:
    """``A^T A`` via the tiled Pallas kernel (padded); fp32 out.

    Zero-padding is exact for the Gram product: padded rows/cols contribute
    zero, and the result is cropped back to (n, n).
    """
    if interpret is None:
        interpret = _interpret()
    m, n = A.shape
    bn_eff = min(bn, max(128, 1 << (n - 1).bit_length()))
    Ap = _pad_to(A, (bk, bn_eff))
    B = _gram.gram(Ap, bn=bn_eff, bk=bk, symmetric=symmetric,
                   interpret=interpret)
    return B[:n, :n]


def matvec(A: jax.Array, v: jax.Array, *, bm: int = 512, bn: int = 512,
           interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = _interpret()
    m, n = A.shape
    Ap = _pad_to(A, (bm, bn))
    vp = _pad_to(v, (bn,))
    return _dm.matvec(Ap, vp, bm=bm, bn=bn, interpret=interpret)[:m]


def deflate_rmatvec(A, U, Xv, SVtv, *, bm: int = 512, bn: int = 512,
                    interpret: bool | None = None):
    """Fused Alg-4 reverse sweep (padded); ``k`` is lane-padded to 128.

    The ``(bm, k)`` U tiles put k on the lane axis; zero columns of U
    paired with zero SVtv entries leave the correction unchanged, and
    the extra ``utxv`` rows they produce are zero — cropped on return.
    """
    if interpret is None:
        interpret = _interpret()
    m, n = A.shape
    k = U.shape[1]
    Ap = _pad_to(A, (bm, bn))
    Up = _pad_to(U, (bm, _LANE))
    Xvp = _pad_to(Xv, (bm,))
    SVtvp = _pad_to(SVtv, (_LANE,))
    t13, utxv = _dm.deflate_rmatvec(Ap, Up, Xvp, SVtvp, bm=bm, bn=bn,
                                    interpret=interpret)
    return t13[:n], utxv[:k]


def block_matvec(A, Q, *, bm: int = 512, bn: int = 512,
                 interpret: bool | None = None, dtype=None):
    """``A @ Q`` via the multi-vector Pallas kernel (padded); fp32 out.

    Zero rows/cols of the padding contribute nothing; Q's padded rows
    multiply padded columns of A only, and its zero-padded k columns
    (lane alignment) yield zero output columns — cropping is exact.
    ``dtype`` is the sweep dtype of the precision policy (operands cast,
    fp32 accumulate).
    """
    if interpret is None:
        interpret = _interpret()
    m, n = A.shape
    k = Q.shape[1]
    Ap = _pad_to(A, (bm, bn))
    Qp = _pad_to(Q, (bn, _LANE))
    return _bm.block_matvec(Ap, Qp, bm=bm, bn=bn, interpret=interpret,
                            dtype=dtype)[:m, :k]


def block_rmatvec(A, Y, *, bm: int = 512, bn: int = 512,
                  interpret: bool | None = None, dtype=None):
    """``A^T @ Y`` via the multi-vector Pallas kernel (padded); fp32 out.

    ``Y``'s k dimension is lane-padded with zero columns (exact); see
    ``block_matvec`` for the ``dtype`` policy.
    """
    if interpret is None:
        interpret = _interpret()
    m, n = A.shape
    k = Y.shape[1]
    Ap = _pad_to(A, (bm, bn))
    Yp = _pad_to(Y, (bm, _LANE))
    return _bm.block_rmatvec(Ap, Yp, bm=bm, bn=bn, interpret=interpret,
                             dtype=dtype)[:n, :k]


def block_gram_chain(A, Q, *, bm: int = 256, bn: int = 8192,
                     interpret: bool | None = None, dtype=None):
    """``A^T (A Q)`` via the one-sweep Pallas kernel (padded); fp32 out.

    ``A`` is zero-padded to whole ``(bm, bn)`` tiles (zero rows and
    columns add nothing to either half; callers that must not copy
    ``A`` pick tiles with ``block_matvec.chain_tiles``) and ``Q``'s
    ``l`` columns to a multiple of 8 sublanes (zero columns of ``Q``
    give zero columns of ``Z``), so cropping ``Z`` to ``(n, l)`` is
    exact.  ``dtype`` is the sweep dtype of the precision policy.
    """
    if interpret is None:
        interpret = _interpret()
    m, n = A.shape
    l = Q.shape[1]
    bn = min(bn, -(-n // _LANE) * _LANE)
    Ap = _pad_to(A, (bm, bn))
    Qp = _pad_to(Q, (bn, 8))
    return _bm.block_gram_chain(Ap, Qp, bm=bm, bn=bn,
                                interpret=interpret, dtype=dtype)[:n, :l]


def local_attention(q, k, v, *, window: int, softcap: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """Causal windowed flash attention; pads S to tile multiple.

    Padding is appended at the sequence end: padded queries produce garbage
    rows that are cropped; padded keys sit *after* every real query so the
    causal mask removes them — exactness is asserted in the tests.
    """
    if interpret is None:
        interpret = _interpret()
    B, H, S, D = q.shape
    qp = _pad_to(q, (1, 1, bq, 1))
    kp = _pad_to(k, (1, 1, bk, 1))
    vp = _pad_to(v, (1, 1, bk, 1))
    Sp = max(qp.shape[2], kp.shape[2])
    qp = _pad_to(qp, (1, 1, Sp, 1)) if qp.shape[2] != Sp else qp
    kp = _pad_to(kp, (1, 1, Sp, 1)) if kp.shape[2] != Sp else kp
    vp = _pad_to(vp, (1, 1, Sp, 1)) if vp.shape[2] != Sp else vp
    out = _la.local_attention(qp, kp, vp, window=window, softcap=softcap,
                              bq=bq, bk=bk, interpret=interpret)
    return out[:, :, :S]


# Re-export oracles for convenience in tests/benchmarks.
gram_ref = _ref.gram_ref
matvec_ref = _ref.matvec_ref
block_matvec_ref = _ref.block_matvec_ref
block_rmatvec_ref = _ref.block_rmatvec_ref
block_gram_chain_ref = _ref.block_gram_chain_ref
deflate_rmatvec_ref = _ref.deflate_rmatvec_ref
local_attention_ref = _ref.local_attention_ref
