"""Planted-spectrum test matrices, made on the device from a seed.

A copy of the generator in ``chip_smoke.py`` (``Planted``, ``_fill``,
``sharded_fill_fn``), kept here so that the benchmark's inputs and its
reference do not change when the program's files do.

``A = U_p diag(s) V_p^T + N``: ``U_p``/``V_p`` have orthonormal columns
(QR of a Gaussian, on the device), the rank is ``r = 2k``, the top ``k``
singular values fall geometrically from 10 to 1, the tail starts at
``gap * s[k-1]`` and falls 10x, and ``N`` is Gaussian noise whose
spectral norm stays below ``noise_rel * s[k-1]`` (Davidson-Szarek,
failure probability ``e^-18``).  The float64 reference for the leading
``k`` singular triplets is ``(U_p, s, V_p)`` itself.

Three layouts, one per memory tier: ``dense()`` fills the matrix in
place on one device, ``sharded(mesh)`` fills each row slab on its own
device, and ``host()`` makes the matrix on the device a chunk of rows at
a time and copies each chunk into one host array.
"""
from __future__ import annotations

import functools
import mmap
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

#: a generated row block stays under this many bytes on the device
GEN_BLOCK_BYTES = 512 << 20
#: Davidson-Szarek: P(||G||_2 > sqrt(m) + sqrt(n) + t) <= exp(-t^2 / 2)
_DS_T = 6.0


def planted_spectrum(k: int, gap: float = 0.3) -> np.ndarray:
    """``r = 2k`` singular values: the top ``k`` fall geometrically from
    10 to 1, the tail starts at ``gap * s[k-1]`` and falls 10x.  Rounded
    through fp32, since that is what the generated matrix holds."""
    top = np.geomspace(10.0, 1.0, k)
    tail = gap * top[-1] * np.geomspace(1.0, 0.1, k)
    return np.concatenate([top, tail]).astype(np.float32).astype(np.float64)


def noise_tau(m: int, n: int, bound: float) -> float:
    """Entry std of Gaussian noise whose spectral norm stays below
    ``bound`` except with probability ``exp(-_DS_T**2 / 2)``."""
    return bound / (np.sqrt(m) + np.sqrt(n) + _DS_T)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative integer seed: the low 32 bits seed
    the key and the high bits are folded in, so seeds past 2**32 do not
    collide."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _row_blocks(m: int, n: int) -> int:
    """Fewest row blocks that divide ``m`` and keep a block under
    ``GEN_BLOCK_BYTES``."""
    nb = max(1, -(-m * n * 4 // GEN_BLOCK_BYTES))
    while m % nb:
        nb += 1
    return nb


class Planted:
    """The factors of one planted matrix, on the default device.

    ``U`` is ``(m, r)`` and ``V`` is ``(n, r)``, both with orthonormal
    columns; ``W = (V diag(s))^T`` is ``(r, n)``; row block ``b`` of
    ``A`` is ``U[rows] @ W`` plus noise drawn from ``fold_in(key, b)``.
    """

    def __init__(self, m: int, n: int, k: int, seed: int,
                 noise_rel: float = 0.0, gap: float = 0.3):
        self.m, self.n, self.k = m, n, k
        self.s = planted_spectrum(k, gap)
        r = self.s.size
        if r > min(m, n):
            raise ValueError(f"rank {r} exceeds {(m, n)}")
        self.bound = noise_rel * float(self.s[k - 1])   # B >= ||N||_2
        self.tau = noise_tau(m, n, self.bound)
        ku, kv, self.key = jax.random.split(seed_key(seed), 3)
        with jax.default_matmul_precision("highest"):
            self.U = _orthonormal(ku, m, r)
            self.V = _orthonormal(kv, n, r)
            self.W = (self.V * jnp.asarray(self.s, jnp.float32)).T

    def dense(self) -> jax.Array:
        """The whole ``(m, n)`` matrix on the default device, filled in
        place block by block (peak: ``A`` plus one block)."""
        nb = _row_blocks(self.m, self.n)
        with jax.default_matmul_precision("highest"):
            return _fill(self.U, self.W, self.key, self.tau,
                         rows=self.m // nb)

    def sharded(self, mesh) -> jax.Array:
        """The matrix row-sharded over ``mesh``'s ``data`` axis, each
        slab generated on its own device (never whole anywhere)."""
        d = mesh.shape["data"]
        if self.m % d:
            raise ValueError(f"m={self.m} not divisible by {d} devices")
        m_loc = self.m // d
        rows = m_loc // _row_blocks(m_loc, self.n)
        U = jax.device_put(self.U, NamedSharding(mesh, P("data", None)))
        with jax.default_matmul_precision("highest"):
            return sharded_fill_fn(mesh, rows)(U, self.W, self.key,
                                               jnp.float32(self.tau))

    def host(self) -> np.ndarray:
        """The matrix as a host ``np.ndarray``, in chunks of rows of at
        most ``HOST_CHUNK_BYTES``, each made on the device and copied
        into one host array by ``HOST_COPIERS`` threads at once.

        A fresh page costs more to fault in than to fill on the chip's
        host, so the array's pages are populated in one call
        (``MAP_POPULATE``) before the copies, and the chunks stay small
        enough for the copy buffers to be reused.
        """
        rows = _chunk_rows(self.m, self.n * 4, HOST_CHUNK_BYTES)
        out = _populated((self.m, self.n))

        def fill(b):
            with jax.default_matmul_precision("highest"):
                blk = _chunk(self.U, self.W, self.key, self.tau, b,
                             rows=rows)
            out[b * rows:(b + 1) * rows] = np.asarray(blk)

        with ThreadPoolExecutor(HOST_COPIERS) as pool:
            list(pool.map(fill, range(self.m // rows)))
        return out


#: a chunk of the host matrix made on the device stays under this size
HOST_CHUNK_BYTES = 32 << 20
#: threads that copy chunks of the host matrix from the device at once
HOST_COPIERS = 4


def _chunk_rows(m: int, row_bytes: int, limit: int) -> int:
    """Most rows that divide ``m`` and stay under ``limit`` bytes."""
    rows = max(1, min(m, limit // row_bytes))
    while m % rows:
        rows -= 1
    return rows


def _populated(shape) -> np.ndarray:
    """An uninitialised float32 host array whose pages are faulted in
    up front where the platform can (``MAP_POPULATE``)."""
    nbytes = int(np.prod(shape)) * 4
    flags = (mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS
             | getattr(mmap, "MAP_POPULATE", 0))
    return np.frombuffer(mmap.mmap(-1, nbytes, flags=flags),
                         np.float32).reshape(shape)


@functools.partial(jax.jit, static_argnames=("m", "r"))
def _orthonormal(key, m: int, r: int):
    return jnp.linalg.qr(jax.random.normal(key, (m, r), jnp.float32))[0]


@jax.jit
def _block(U_rows, W, key, tau):
    noise = jax.random.normal(key, (U_rows.shape[0], W.shape[1]),
                              jnp.float32)
    return U_rows @ W + tau * noise


@functools.partial(jax.jit, static_argnames=("rows",))
def _chunk(U, W, key, tau, b, *, rows: int):
    """Rows ``[b * rows, (b + 1) * rows)`` of ``A``."""
    return _block(jax.lax.dynamic_slice_in_dim(U, b * rows, rows), W,
                  jax.random.fold_in(key, b), tau)


def _fill_body(U, W, key, tau, rows: int, vary=None):
    m, n = U.shape[0], W.shape[1]
    A0 = jnp.zeros((m, n), jnp.float32)
    if vary:
        A0 = jax.lax.pcast(A0, (vary,), to="varying")

    def body(b, A):
        lo = b * rows
        blk = _block(jax.lax.dynamic_slice_in_dim(U, lo, rows), W,
                     jax.random.fold_in(key, b), tau)
        return jax.lax.dynamic_update_slice_in_dim(A, blk, lo, axis=0)

    return jax.lax.fori_loop(0, m // rows, body, A0)


@functools.partial(jax.jit, static_argnames=("rows",))
def _fill(U, W, key, tau, *, rows: int):
    return _fill_body(U, W, key, tau, rows)


@functools.lru_cache(maxsize=None)
def sharded_fill_fn(mesh, rows: int):
    """jitted ``(U, W, key, tau) -> A`` row-sharded over ``mesh``'s
    ``data`` axis: each device fills its own slab from its own rows of
    ``U`` and its own noise stream."""

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P("data", None), P(), P(), P()),
                       out_specs=P("data", None))
    def fill_local(U_loc, W, key, tau):
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        return _fill_body(U_loc, W, key, tau, rows, vary="data")

    return jax.jit(fill_local)
