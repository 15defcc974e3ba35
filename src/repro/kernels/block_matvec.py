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
* ``block_gram_chain`` — ``Z = A^T (A Q)`` in ONE sweep: grid ``(m/bm,)``
  over row tiles; each tile is read from HBM once and serves both
  halves while it sits in VMEM, with ``Q^T`` and ``Z^T`` held ``(l, n)``
  (lane-dense along n).  ``core/operator.py::_dense_chain`` runs it on
  a TPU.

All three take a ``dtype`` (the ``sweep_dtype`` of the mixed-precision
policy, ``repro/core/precision.py``).  The two single sweeps cast their
operands before the kernel, so the tiles stream through VMEM at that
width; the chain casts each tile in VMEM, so ``A`` is read at its stored
width and never copied in HBM.  Every ``dot_general`` keeps
``preferred_element_type=float32`` (and float32 operands
``Precision.HIGHEST``), so the MXU accumulates in fp32 and the output
is always fp32.  ``dtype=None`` (default) leaves the operands untouched.

The single sweeps require ``m % bm == n % bn == 0`` AND a lane-aligned
``k`` (the RHS tile's last dimension maps to the 128-wide lane axis;
Mosaic rejects arbitrary ``k`` on real TPU); the chain requires whole
tiles and ``l`` a multiple of 8 sublanes — ``ops.py`` pads and crops.

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
from jax.experimental.pallas import tpu as pltpu


def _cast(x: jax.Array, dtype) -> jax.Array:
    return x if dtype is None else x.astype(dtype)


def _precision(x: jax.Array):
    """Mosaic's default contracts fp32 operands in ONE bf16 pass (the
    measured error of an "fp32" chain matched the bf16 one); fp32
    operands ask for the fp32 contraction explicitly, and bf16 ones for
    the default, which Mosaic requires even under an ambient
    ``default_matmul_precision("highest")``."""
    if x.dtype == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


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
# Fused chain: Z = A^T (A Q) in one sweep over A's row tiles
# ---------------------------------------------------------------------------

def _chain_kernel(qt_ref, a_ref, z_ref, *, bn: int, dtype):
    """One row tile ``A_i`` per grid step: ``Y_i = A_i Q`` and then
    ``Z += A_i^T Y_i`` from the same VMEM copy of the tile.

    ``qt_ref`` and ``z_ref`` hold ``Q^T`` and ``Z^T``, ``(l, n)``, lane-
    dense along n; ``Z^T`` stays resident across the sequential grid.
    Both halves walk the tile in ``bn``-column chunks, cast to the sweep
    dtype in VMEM.
    """
    i = pl.program_id(0)
    l = z_ref.shape[0]
    bm, n = a_ref.shape
    nc = n // bn

    @pl.when(i == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    def chunk(c):
        cols = pl.ds(pl.multiple_of(c * bn, bn), bn)
        return cols, a_ref[:, cols].astype(dtype)

    def forward(c, yt):                       # Y_i^T += Q_c^T A_ic^T
        cols, a = chunk(c)
        return yt + jax.lax.dot_general(
            qt_ref[:, cols], a, (((1,), (1,)), ((), ())),
            precision=_precision(a), preferred_element_type=jnp.float32)

    yt = jax.lax.fori_loop(0, nc, forward,
                           jnp.zeros((l, bm), jnp.float32)).astype(dtype)

    def backward(c, carry):                   # Z_c^T += Y_i^T A_ic
        cols, a = chunk(c)
        z_ref[:, cols] += jnp.dot(yt, a, precision=_precision(a),
                                  preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, nc, backward, 0)


#: VMEM the fused chain may plan for (a v5e core has 128 MiB; the
#: compiler gets 16 MiB more for its own temporaries)
CHAIN_VMEM_BYTES = 100 << 20


def chain_vmem_bytes(bm: int, bn: int, n: int, l: int, a_itemsize: int,
                     dtype) -> int:
    """VMEM the fused chain holds: the double-buffered ``A`` tile, one
    float32 column chunk of it and its cast under a narrower sweep
    dtype, and ``Q^T`` and the resident ``Z^T`` (both double-buffered)."""
    sd = jnp.dtype(dtype).itemsize
    cast = bm * bn * sd if sd != a_itemsize else 0
    return 2 * bm * n * a_itemsize + bm * bn * 4 + cast \
        + 2 * l * n * (sd + 4)


def chain_tiles(m: int, n: int, l: int, dtype,
                a_itemsize: int = 4) -> tuple[int, int] | None:
    """``(bm, bn)`` for ``block_gram_chain`` on an ``(m, n)`` ``A`` and an
    ``l``-wide ``Q`` without padding ``A``, or None where the shapes do
    not tile or VMEM cannot hold a row tile, ``Q`` and ``Z``.  Prefers
    256-row tiles and 8192-column chunks (the fastest on a v5e at
    65536 x 32768, l = 40: 14.2 ms against 17.8 ms at 128 x 1024)."""
    if n % 128:
        return None
    bn = next(b for b in (8192, 4096, 2048, 1024, 512, 256, 128)
              if n % b == 0)
    lp = -(-l // 8) * 8
    for bm in (256, 128):
        if m % bm == 0 and chain_vmem_bytes(
                bm, bn, n, lp, a_itemsize, dtype) <= CHAIN_VMEM_BYTES:
            return bm, bn
    return None


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "interpret", "dtype"))
def block_gram_chain(A: jax.Array, Q: jax.Array, *, bm: int = 256,
                     bn: int = 8192, interpret: bool = False,
                     dtype=None) -> jax.Array:
    """``Z = A^T (A Q)`` reading each row tile of ``A`` once; A: (m, n),
    Q: (n, l) -> (n, l) float32.

    The grid runs over ``bm``-row tiles of ``A`` only.  While a tile
    sits in VMEM the kernel forms ``Y_i = A_i Q`` and accumulates
    ``Z += A_i^T Y_i`` into a ``Z`` that stays resident across the
    grid, so one chain reads ``A`` from HBM once.  ``Q`` and ``Z`` are
    held transposed, ``(l, n)``, so ``n`` lies along the lanes and
    ``l`` is not padded to 128.  This is the per-iteration operator of
    the subspace iterate (``core/operator.py::_dense_chain``).

    ``dtype`` is the sweep dtype (``None``: ``A``'s own).  float32
    operands contract at ``Precision.HIGHEST``; bfloat16 casts each
    chunk of the tile in VMEM (``A`` is never copied in HBM) and rounds
    ``Y`` to bf16 before the reverse half, as ``ref.block_gram_chain_ref``
    does; accumulation is float32 either way.  Requires ``m % bm == 0``,
    ``n % bn == 0``, ``bm`` and ``bn`` multiples of 128 and ``l`` a
    multiple of 8 (``ops.block_gram_chain`` pads; ``chain_tiles`` picks
    tiles that need no padding of ``A``).
    """
    m, n = A.shape
    l = Q.shape[1]
    sd = jnp.dtype(A.dtype if dtype is None else dtype)
    if m % bm or n % bn:
        raise ValueError(f"shape {(m, n)} not divisible by {(bm, bn)}")
    vmem = chain_vmem_bytes(bm, bn, n, l, A.dtype.itemsize, sd)
    zt = pl.pallas_call(
        functools.partial(_chain_kernel, bn=bn, dtype=sd),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((l, n), lambda i: (0, 0)),
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((l, n), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((l, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
    )(Q.astype(sd).T, A)
    return zt.T
