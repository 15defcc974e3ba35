"""Pallas TPU kernels for the block (subspace) power step (beyond-paper).

The block method iterates ``Q <- orth(A^T (A Q))`` on an ``(n, k)`` block,
so its hot loop is two *multi-vector* mat-vecs.  These kernels reuse the
``gram``/``deflate_matvec`` tiling with the 1-column RHS widened to the
full ``k``-column block:

* ``block_matvec``  — ``Y = A @ Q``:   grid ``(m/bm, n/bn)`` with the
  reduction (n) innermost; the RHS tile is ``(bn, k)`` so one pass of
  ``A`` tiles through VMEM advances all k columns.  Per tile the MXU does
  ``(bm, bn) x (bn, k)`` — k times the arithmetic of the single-vector
  kernel on the SAME bytes of ``A``, which is what turns the memory-bound
  power step compute-dense.
* ``block_rmatvec`` — ``Z = A^T @ Y``: grid ``(n/bn, m/bm)`` with the
  reduction (m) innermost, ``(bm, k)`` RHS tiles, accumulating ``(bn, k)``
  output tiles resident in VMEM.

All three entry points take a ``dtype`` (the ``sweep_dtype`` of the
mixed-precision policy, ``repro/core/precision.py``): operands are cast
before the kernel so the tiles stream through VMEM at that width — bf16
halves the HBM bytes of the dominant ``A`` traffic — while every
``dot_general`` keeps ``preferred_element_type=float32``, so the MXU
accumulates in fp32 and the output is always fp32.  ``dtype=None``
(default) leaves the operands untouched.

The raw kernels require ``m % bm == n % bn == 0`` AND a lane-aligned
``k`` (the RHS tile's last dimension maps to the 128-wide lane axis;
Mosaic rejects arbitrary ``k`` on real TPU) — ``ops.py`` pads both and
crops on return.

As everywhere in this package, Mosaic's grid pipeline DMAs the next tiles
while the MXU chews the current ones — the CUDA-stream overlap of the
paper's Alg 3 — and ``ref.py`` holds the pure-jnp oracles the tests sweep
against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _cast(x: jax.Array, dtype) -> jax.Array:
    return x if dtype is None else x.astype(dtype)


def _precision(x: jax.Array):
    """Mosaic's default contracts fp32 operands in ONE bf16 pass (the
    measured error of an "fp32" chain matched the bf16 one); fp32
    operands ask for the fp32 contraction explicitly."""
    return jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None


# ---------------------------------------------------------------------------
# Forward sweep: Y = A @ Q
# ---------------------------------------------------------------------------

def _block_matvec_kernel(a_ref, q_ref, y_ref):
    """Grid (m_blocks, n_blocks); n (reduction) innermost."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    a = a_ref[...]            # (bm, bn)
    q = q_ref[...]            # (bn, k)
    y_ref[...] += jax.lax.dot_general(
        a, q, (((1,), (0,)), ((), ())), precision=_precision(a),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret", "dtype"))
def block_matvec(A: jax.Array, Q: jax.Array, *, bm: int = 512,
                 bn: int = 512, interpret: bool = False,
                 dtype=None) -> jax.Array:
    """``A @ Q`` tiled; A: (m, n), Q: (n, k) -> (m, k) fp32.

    ``dtype`` casts both operands to the sweep dtype (fp32 accumulate).
    """
    A, Q = _cast(A, dtype), _cast(Q, dtype)
    m, n = A.shape
    k = Q.shape[1]
    if m % bm or n % bn:
        raise ValueError(f"shape {(m, n)} not divisible by {(bm, bn)}")
    return pl.pallas_call(
        _block_matvec_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(A, Q)


# ---------------------------------------------------------------------------
# Reverse sweep: Z = A^T @ Y
# ---------------------------------------------------------------------------

def _block_rmatvec_kernel(a_ref, y_ref, z_ref):
    """Grid (n_blocks, m_blocks); m (reduction) innermost."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    a = a_ref[...]            # (bm, bn)
    y = y_ref[...]            # (bm, k)
    z_ref[...] += jax.lax.dot_general(
        a, y, (((0,), (0,)), ((), ())),  # a^T @ y
        precision=_precision(a), preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret", "dtype"))
def block_rmatvec(A: jax.Array, Y: jax.Array, *, bm: int = 512,
                  bn: int = 512, interpret: bool = False,
                  dtype=None) -> jax.Array:
    """``A^T @ Y`` tiled; A: (m, n), Y: (m, k) -> (n, k) fp32.

    ``dtype`` casts both operands to the sweep dtype (fp32 accumulate).
    """
    A, Y = _cast(A, dtype), _cast(Y, dtype)
    m, n = A.shape
    k = Y.shape[1]
    if m % bm or n % bn:
        raise ValueError(f"shape {(m, n)} not divisible by {(bm, bn)}")
    return pl.pallas_call(
        _block_rmatvec_kernel,
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, k), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(A, Y)


# ---------------------------------------------------------------------------
# Fused chain: Z = A^T (A Q) — the block power step / range-finder sweep
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret", "dtype"))
def block_gram_chain(A: jax.Array, Q: jax.Array, *, bm: int = 512,
                     bn: int = 512, interpret: bool = False,
                     dtype=None) -> jax.Array:
    """``Z = A^T (A Q)`` — one full block power sweep; A: (m, n), Q: (n, k).

    Reuses the two multi-vector kernels back-to-back (each keeps its own
    Mosaic grid pipeline over ``A``'s tiles); the only extra HBM traffic
    beyond the two sweeps of ``A`` is the skinny fp32 ``(m, k)``
    intermediate ``Y``, which is negligible for ``k << n``.  This is the
    per-iteration operator of the subspace iterate AND of the randomized
    range-finder warm start ``orth((A^T A)^q A^T Omega)``.

    Under ``dtype=bfloat16`` the cast of ``A`` happens once here, both
    sweeps stream the 2-byte copy, and the fp32-accumulated intermediate
    ``Y`` is cast back down for the reverse sweep (the policy's
    "operands low, accumulation fp32" contract).
    """
    A = _cast(A, dtype)                       # cast once, both sweeps reuse
    Y = block_matvec(A, Q, bm=bm, bn=bn, interpret=interpret, dtype=dtype)
    return block_rmatvec(A, Y, bm=bm, bn=bn, interpret=interpret,
                         dtype=dtype)
