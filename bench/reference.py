"""The plain reference: products with ``A`` and a textbook truncated SVD.

Nothing here imports the program under test.  ``RefOps`` applies ``A``
and ``A^T`` in row chunks, on whichever layout the benchmark made
(one device, row-sharded over a mesh, or host row blocks streamed to the
device); the check reads its residuals through it.  ``tsvd`` is plain
block subspace iteration, ``Q <- qr(A^T A Q)`` a fixed number of times,
then Rayleigh-Ritz from ``W = A Q``.

Every product takes a precision.  ``"highest"`` is a float32 dot at
``Precision.HIGHEST``.  ``"high"`` is three bfloat16 passes with float32
accumulation (``hi*hi + hi*lo + lo*hi``), what XLA:TPU runs for a float32
dot at ``Precision.HIGH``; it is written out so that it means the same on
every backend.  ``tsvd`` at ``"high"`` is the control: the reference
computed in the nearest precision below the configuration's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.datagen import seed_key

PRECISIONS = ("highest", "high")
#: rows of ``A`` one chunk of a product reads
CHUNK_ROWS = 2048


def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def dot(a, b, precision: str):
    """``a @ b`` in float32 at ``precision``."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"precision must be one of {PRECISIONS}")
    a1, a2 = _split(a)
    b1, b2 = _split(b)
    d = functools.partial(jnp.matmul, preferred_element_type=jnp.float32)
    return d(a1, b1) + (d(a1, b2) + d(a2, b1))


def _chunks(m: int) -> int:
    c = min(CHUNK_ROWS, m)
    while m % c:
        c -= 1
    return c


def _mm_local(A, X, precision):
    """``A @ X`` over row chunks of ``A``."""
    m, n = A.shape
    c = _chunks(m)
    out = jax.lax.map(lambda blk: dot(blk, X, precision),
                      A.reshape(m // c, c, n))
    return out.reshape(m, X.shape[1])


def _rmm_local(A, Y, precision):
    """``A^T @ Y`` accumulated over row chunks of ``A``."""
    m, n = A.shape
    c = _chunks(m)

    def body(i, acc):
        blk = jax.lax.dynamic_slice_in_dim(A, i * c, c)
        yb = jax.lax.dynamic_slice_in_dim(Y, i * c, c)
        return acc + dot(blk.T, yb, precision)

    # the first chunk starts the sum, so the carry varies over a mesh
    # axis exactly as the products do inside a shard_map
    return jax.lax.fori_loop(1, m // c, body, dot(A[:c].T, Y[:c], precision))


_mm_dense = jax.jit(_mm_local, static_argnames=("precision",))
_rmm_dense = jax.jit(_rmm_local, static_argnames=("precision",))


@functools.lru_cache(maxsize=None)
def _sharded_fns(mesh, precision):
    rows = P("data", None)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(rows, P()),
                       out_specs=rows)
    def mm(A, X):
        return _mm_local(A, X, precision)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(rows, rows),
                       out_specs=P())
    def rmm(A, Y):
        return jax.lax.psum(_rmm_local(A, Y, precision), "data")

    return jax.jit(mm), jax.jit(rmm)


class RefOps:
    """``A @ X`` and ``A^T @ Y`` on the benchmark's own copy of ``A``.

    ``A`` is a ``jax.Array`` on one device, a ``jax.Array`` row-sharded
    over ``mesh``'s ``data`` axis, or a host ``np.ndarray`` that is
    streamed to the device in ``n_blocks`` row blocks.
    """

    def __init__(self, A, *, mesh=None, n_blocks: int = 1):
        self.A, self.mesh, self.n_blocks = A, mesh, n_blocks
        self.m, self.n = A.shape
        #: passes over ``A`` one ``chain`` takes
        self.chain_passes = 1 if isinstance(A, np.ndarray) else 2

    def _stream(self, step, acc):
        """Fold ``step(acc, lo, block)`` over the host row blocks, one
        block on the device at a time."""
        rows = self.m // self.n_blocks
        for b in range(self.n_blocks):
            lo = b * rows
            acc = jax.block_until_ready(
                step(acc, lo, jnp.asarray(self.A[lo:lo + rows])))
        return acc

    def mm(self, X, precision: str = "highest"):
        if isinstance(self.A, np.ndarray):
            return jnp.concatenate(self._stream(
                lambda acc, lo, blk: acc + [_mm_dense(blk, X,
                                                      precision=precision)],
                []))
        if self.mesh is not None:
            X = jax.device_put(X, NamedSharding(self.mesh, P()))
            return _sharded_fns(self.mesh, precision)[0](self.A, X)
        return _mm_dense(self.A, X, precision=precision)

    def rmm(self, Y, precision: str = "highest"):
        if isinstance(self.A, np.ndarray):
            return self._stream(
                lambda acc, lo, blk: acc + _rmm_dense(
                    blk, Y[lo:lo + blk.shape[0]], precision=precision),
                jnp.zeros((self.n, Y.shape[1]), jnp.float32))
        if self.mesh is not None:
            Y = jax.device_put(Y, NamedSharding(self.mesh,
                                                P("data", None)))
            return _sharded_fns(self.mesh, precision)[1](self.A, Y)
        return _rmm_dense(self.A, Y, precision=precision)

    def chain(self, Q, precision: str = "highest"):
        """``A^T (A Q)``: one stream of the host blocks, or two products
        with a device-resident ``A``."""
        if isinstance(self.A, np.ndarray):
            return self._stream(
                lambda acc, lo, blk: acc + _rmm_dense(
                    blk, _mm_dense(blk, Q, precision=precision),
                    precision=precision),
                jnp.zeros((self.n, Q.shape[1]), jnp.float32))
        return self.rmm(self.mm(Q, precision), precision)


class RefResult:
    """What ``tsvd`` returns, under the names the harness reads."""

    def __init__(self, U, S, V, iters, passes, backend):
        self.U, self.S, self.V = U, S, V
        self.iters = np.full((S.shape[0],), iters, np.int32)
        self.passes_over_A = passes
        self.backend = backend
        self.bytes_moved = None
        self.faults = None


@functools.partial(jax.jit, static_argnames=("precision",))
def _orth(Z, precision):
    with jax.default_matmul_precision(precision):
        return jnp.linalg.qr(Z)[0]


@functools.partial(jax.jit, static_argnames=("precision",))
def _rayleigh_ritz(W, Q, precision):
    with jax.default_matmul_precision(precision):
        Uw, R = jnp.linalg.qr(W)
        Us, S, Vh = jnp.linalg.svd(R)
        return dot(Uw, Us, precision), S, dot(Q, Vh.T, precision)


def tsvd(ops: RefOps, k: int, seed: int, iters: int, *,
         precision: str = "highest",
         backend: str = "reference") -> RefResult:
    """Leading ``k`` singular triplets of ``A`` by ``iters`` block
    subspace iterations from a Gaussian start drawn from ``seed``."""
    key = seed_key(seed)
    Q = _orth(jax.random.normal(key, (ops.n, k), jnp.float32), precision)
    for _ in range(iters):
        Q = _orth(ops.chain(Q, precision), precision)
    U, S, V = _rayleigh_ritz(ops.mm(Q, precision), Q, precision)
    return RefResult(U, S, V, iters, ops.chain_passes * iters + 1, backend)
