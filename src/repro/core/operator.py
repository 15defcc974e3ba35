"""The blocked-operator protocol behind the single SVD front door.

The paper specializes ONE algorithm (block/power subspace iteration with
batched Gram sweeps) to four execution regimes; related out-of-core work
(Lu et al., arXiv:1706.07191; Demchik et al., arXiv:1907.06470) frames
the same split as one solver over a blocked-operator abstraction.  This
module is that abstraction: ``LinearOperator`` defines exactly the
surface the shared block-iteration driver (``core/svd.py``) needs, and
four adapters map the repo's execution regimes onto it:

* ``DenseOperator``        — an in-memory jax array (serial).
* ``ShardedOperator``      — a row-sharded jax array over mesh axes;
  every A-sized product is a ``shard_map`` with ONE fused psum.
* ``HostBlockedOperator``  — wraps a ``HostBlockedMatrix``: host-resident
  row blocks streamed H2D (degree-1 out-of-core).
* ``MemmapOperator``       — wraps a ``MemmapMatrix`` (``core/diskio.py``):
  disk-resident row blocks staged disk->host->device under a bounded
  host budget (the full memory hierarchy).
* ``SparseStreamOperator`` — wraps a procedural sparse matrix (or any
  object with the streamed ``matmat``/``rmatmat``/``gram_chain``/
  ``range_sketch`` surface, e.g. ``DenseStreamOperator`` or the scipy
  CSR/COO adapter ``core/sparse.py::ScipySparseMatrix``; the
  ``ScipySparseOperator`` subclass there tags real-dataset runs).

The protocol:

``shape``/``dtype``        logical (M, N) and element type.
``matmat``/``rmatmat``     exact (fp32) operator application — the
                           Rayleigh–Ritz extraction pass.
``gram_chain``             the hot loop's ``A^T (A Q)`` sweep, honoring
                           the operator's ``sweep_dtype`` policy.
``range_sketch``           ``A^T Omega`` with operator-native RNG — the
                           randomized range-finder sketch.
``random_block``/``orth``/``subspace_gap``/``extract``
                           the remaining driver primitives, with shared
                           defaults (QR orthonormalization, rotation-
                           invariant subspace test, Rayleigh–Ritz from
                           ``W = A Q``).
``passes``/``bytes_per_pass``
                           accounting.  Every A-sized call increments
                           ``passes`` by its true cost: XLA's dense and
                           sharded chains read ``A`` twice per
                           ``gram_chain`` (``chain_passes = 2``); the
                           streamed backends and the dense Pallas chain
                           on a TPU fuse both halves into ONE read of
                           the data (``chain_passes = 1``).
                           ``bytes_per_pass``
                           is what one pass moves at the configured
                           sweep dtype, so ``passes * bytes_per_pass``
                           is the dominant data-movement cost.
``bytes_moved``            the per-tier breakdown of that cost: total
                           bytes each memory tier (``disk``/``host``/
                           ``device``) has moved so far.  In-memory
                           backends read ``A`` from device memory; the
                           host-streamed backends move every pass over
                           the host tier too; the memmap backend adds
                           the disk tier (actual file-read counters, so
                           host-cache hits show up as fewer disk bytes).
``lagged_sync``            True when the driver should sync the
                           convergence scalar one iteration late so the
                           host never stalls the operator's async
                           dispatch / prefetch pipeline (every jax
                           backend; the synchronous numpy backend keeps
                           the exact per-iteration check).

Custom backends (memmap files, multi-host, CSR input) subclass
``LinearOperator``, implement the abstract pieces, and get the full
solver — warm start, mixed-precision sweeps, pass accounting — for free
via ``repro.core.svd(op, k, ...)``.
"""
from __future__ import annotations

import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.config import seed_to_key
from repro.core.precision import resolve_sweep_dtype
from repro.core.spans import span
from repro.core.tsvd import (rayleigh_ritz_from_W, sweep_ops,
                             warm_start_width)

__all__ = [
    "LinearOperator",
    "DenseOperator",
    "ShardedOperator",
    "HostBlockedOperator",
    "MemmapOperator",
    "SparseStreamOperator",
    "dense_block_step_fn",
    "fused_chain_tiles",
    "sharded_block_step_fn",
    "host_sync_scalar",
    "warm_start_width",
]


def host_sync_scalar(x):
    """The ONE sanctioned device->host sync in the driver loops.

    Blocks until ``x`` (a 0-d device array, numpy scalar, or plain
    python number) is available and returns it as a python scalar.
    Every per-iteration host read in ``core/`` goes through here so the
    static analyzer (``repro.analysis``, lint rule ANA001) can tell the
    driver's deliberate lagged convergence sync apart from an accidental
    ``float()`` that would stall the async-dispatch / H2D-prefetch
    pipeline once per iteration.
    """
    if isinstance(x, (bool, int, float)):
        return x
    with span("svd.sync"):
        return x.item()


# ---------------------------------------------------------------------------
# Shared jitted primitives (module-level: cached across operator instances)
# ---------------------------------------------------------------------------

@jax.jit
def _orth(X):
    return jnp.linalg.qr(X)[0]


@jax.jit
def _gap(Q, Qn):
    # sum of squared sines of the principal angles between span(Q) and
    # span(Qn): invariant to rotations within the subspace, so it settles
    # even when singular values are clustered (per-column |v . v1| tests
    # never do).  Taken as ||Qn - Q Q^T Qn||_F^2, not l - ||Q^T Qn||_F^2:
    # the difference form cancels to fp32 rounding noise (~1e-5 at
    # n = 32768 on a TPU), a floor at the driver's eps * l tolerance.
    # Returned unsynced — a device scalar the driver floats.
    return jnp.sum((Qn - Q @ (Q.T @ Qn)) ** 2)


def fused_chain_tiles(platform, shape, l, sweep_dtype):
    """The ``(row tile, column chunk)`` of the one-read Pallas chain for
    an ``(m, n)`` float32 operand and an ``l``-wide iterate, or None for
    the XLA two-dot chain.  Only a TPU compiles the kernel; the tiles
    must divide ``A`` (padding would copy it on every call), and VMEM
    must hold a row tile, ``Q`` and ``Z`` (``block_matvec.chain_tiles``).
    """
    if platform != "tpu":
        return None
    # Pallas is imported only where the kernel can run: it adds about
    # a second to every process that imports it
    from repro.kernels.block_matvec import chain_tiles
    return chain_tiles(*shape, l, sweep_dtype)


@functools.partial(jax.jit, static_argnames=("sweep_dtype", "tiles"))
def _dense_chain(X, Q, *, sweep_dtype, tiles=None):
    """``X^T (X Q)``: the one-read Pallas kernel under ``tiles`` (from
    ``fused_chain_tiles``), else ``sweep_ops``' two XLA dots, which
    read ``X`` twice.  Either way one program, ``jit__dense_chain``."""
    if tiles is not None:
        from repro.kernels import ops as kernel_ops
        bm, bn = tiles
        return kernel_ops.block_gram_chain(X, Q, bm=bm, bn=bn,
                                           dtype=sweep_dtype)
    mm, rmm = sweep_ops(X, sweep_dtype)
    return rmm(mm(Q))


@functools.partial(jax.jit, static_argnames=("l", "sweep_dtype"))
def _dense_sketch(X, key, *, l, sweep_dtype):
    _, rmm = sweep_ops(X, sweep_dtype)
    Om = jax.random.normal(jax.random.fold_in(key, 1), (X.shape[0], l),
                           jnp.float32)
    return rmm(Om)


@jax.jit
def _dense_extract(X, Q):
    return rayleigh_ritz_from_W(X @ Q, Q)


@functools.lru_cache(maxsize=None)
def dense_block_step_fn(sweep_dtype, tiles=None):
    """ONE driver block step on the dense backend: the sweep-dtype gram
    chain (the fused kernel under ``tiles``) composed with the shared QR
    orthonormalization — the same two jitted primitives
    ``core/svd.py::step`` dispatches per iteration through
    ``DenseOperator``.  ``repro.analysis`` traces THIS function, so the
    checked schedule can't drift from the solver."""

    def block_step(X, Q):
        return _orth(_dense_chain(X, Q, sweep_dtype=sweep_dtype,
                                  tiles=tiles))

    return jax.jit(block_step)


# ---------------------------------------------------------------------------
# Protocol / base class
# ---------------------------------------------------------------------------

#: serializes first-touch creation of the per-operator solve lock for
#: duck-typed operators that never ran ``LinearOperator.__init__``
_SOLVE_GUARD_INIT = threading.Lock()


class LinearOperator:
    """Base class + protocol for the shared block-iteration driver.

    Subclasses implement ``shape``, ``matmat``, ``rmatmat``,
    ``range_sketch``, ``random_block``, and ``bytes_per_pass``; the
    defaults below supply everything else.  Implementations MUST call
    ``self._count(n)`` once per A-sized sweep so ``passes`` stays the
    ground truth the accounting tests assert against.
    """

    #: passes one ``gram_chain`` costs (2 = two A-sized sweeps; the
    #: streamed backends and the dense one-read kernel fuse both halves
    #: into one read of ``A`` and set 1)
    chain_passes = 2
    #: passes one ``range_sketch`` costs
    sketch_passes = 1
    #: driver syncs the convergence scalar one iteration late (bounded
    #: one-pass overshoot) so the host never stalls a prefetch pipeline
    lagged_sync = False
    #: tag reported in ``SVDResult.backend``
    backend = "operator"

    def __init__(self):
        self._passes = 0
        self._telemetry = None
        self._retry_policy = None
        self._solve_lock = threading.Lock()

    def _count(self, n):
        self._passes += n

    # -- exclusive-solve guard (one driver loop per operator instance) ------

    def acquire_solve(self):
        """Claim this operator for one driver loop.

        The pass/byte counters and the per-solve ``set_resilience``
        telemetry install are instance state: two solves interleaving on
        the SAME operator would silently cross-wire each other's
        accounting and fault records.  A serving process (many jobs, one
        process — ``repro.serving``) must give each job its own operator;
        reusing a live one is a caller error, so it raises the typed 4xx
        ``InputError`` instead of corrupting both jobs.  Non-blocking by
        design: queueing on a busy operator would deadlock a runner pool.
        """
        # lazy init: duck-typed subclasses may never call super().__init__
        lock = self.__dict__.get("_solve_lock")
        if lock is None:
            with _SOLVE_GUARD_INIT:
                lock = self.__dict__.setdefault("_solve_lock",
                                                threading.Lock())
        if not lock.acquire(blocking=False):
            from repro.core.errors import InputError
            raise InputError(
                f"operator {self.fingerprint!r} is already running a "
                f"solve: LinearOperator instances hold per-solve mutable "
                f"state (pass/byte counters, fault telemetry) and cannot "
                f"be shared by concurrent svd() calls — build one "
                f"operator per job (repro.serving does this for you)")

    def release_solve(self):
        """Release the exclusive-solve claim (idempotent: releasing an
        unclaimed operator is a no-op so driver cleanup paths can't
        die on double release)."""
        lock = self.__dict__.get("_solve_lock")
        if lock is not None and lock.locked():
            try:
                lock.release()
            except RuntimeError:  # pragma: no cover - released elsewhere
                pass

    @property
    def passes(self):
        """A-sized operand sweeps performed so far (the accounting)."""
        return self._passes

    def reset_passes(self):
        self._passes = 0

    def reset_counters(self):
        """Zero the pass/byte counters so a solve's delta accounting
        starts from a clean slate (adapters wrapping counting matrices
        forward to them)."""
        self.reset_passes()

    # -- required surface ---------------------------------------------------

    @property
    def shape(self) -> tuple[int, int]:
        raise NotImplementedError

    @property
    def dtype(self):
        return jnp.float32

    def matmat(self, Q):
        """``A @ Q`` at full (fp32) precision — one pass over ``A``."""
        raise NotImplementedError

    def rmatmat(self, Y):
        """``A.T @ Y`` at full (fp32) precision — one pass over ``A``."""
        raise NotImplementedError

    def range_sketch(self, l, seed):
        """``A.T @ Omega``, ``Omega ~ N(0,1)^(M x l)`` generated with the
        operator's native RNG/streaming — one pass over ``A``."""
        raise NotImplementedError

    def random_block(self, k, seed):
        """An (N, k) standard-normal block in the operator's namespace
        (NOT orthonormalized — the driver applies ``orth``)."""
        raise NotImplementedError

    @property
    def bytes_per_pass(self) -> int:
        """Bytes one A-sized pass moves at the configured sweep dtype."""
        raise NotImplementedError

    # -- defaults the adapters may override ---------------------------------

    @property
    def bytes_moved(self) -> dict[str, int]:
        """Total bytes moved so far, per memory tier (disk/host/device).

        The default is the in-memory story: every pass reads ``A`` from
        device memory.  Streamed adapters extend the breakdown with the
        host (H2D) and disk tiers they actually cross.
        """
        return {"device": self.passes * self.bytes_per_pass}

    def gram_chain(self, Q):
        """``A.T @ (A @ Q)`` honoring the sweep-dtype policy.

        Default composes the exact products (two passes, counted by the
        sub-calls); fused/streamed backends override to one stream.
        """
        return self.rmatmat(self.matmat(Q))

    def orth(self, X):
        """Orthonormalize columns (thin-QR Q factor)."""
        return _orth(X)

    def subspace_gap(self, Q, Qn):
        """Rotation-invariant gap ``||Qn - Q Q^T Qn||_F^2`` (equal to
        ``l - ||Q^T Qn||_F^2`` for orthonormal bases; may return an
        unsynced device scalar; the driver floats it)."""
        return _gap(Q, Qn)

    def extract(self, Q):
        """Rayleigh–Ritz extraction from the converged basis: one
        ``matmat`` pass + small QR/SVD factorizations."""
        return rayleigh_ritz_from_W(self.matmat(Q), Q)

    # -- solver-state round-trip (checkpoint/resume, svd_update) ------------

    def to_host(self, X) -> np.ndarray:
        """The iterate as a host fp32 numpy array (checkpoint leaves)."""
        return np.asarray(jax.device_get(X), np.float32)

    def from_host(self, W):
        """A host fp32 array lifted into the operator's array namespace
        (sharded adapters re-replicate/re-place it here)."""
        return jnp.asarray(W, jnp.float32)

    @property
    def fingerprint(self) -> str:
        """Identity of the problem this operator poses — backend, shape,
        element/sweep dtypes.  A checkpoint written under one fingerprint
        refuses to resume under another."""
        m, n = self.shape
        sd = getattr(self, "sweep_dtype", "float32")
        return (f"{self.backend}:{int(m)}x{int(n)}:"
                f"{np.dtype(self.dtype).name}:{sd}")

    # -- resilience (core/faults.py) ----------------------------------------

    def set_resilience(self, telemetry=None, retry_policy=None):
        """Install the per-solve fault telemetry + retry policy.  The
        driver calls this once per solve; adapters wrapping staged
        matrices forward both onto the matrix, whose staging hops run
        the actual ``retry_io`` loops."""
        self._telemetry = telemetry
        self._retry_policy = retry_policy

    def demote(self, cfg):
        """The next-lower memory tier for this problem, as a fresh
        operator carrying the SAME matrix — or None when there is no
        lower tier.  Called by the driver when a step hits device OOM
        (``cfg.demote_on_oom``); the driver re-enters the demoted
        operator with the warm iterate, so the work done so far is
        kept.  The ladder: dense/sharded -> host-blocked -> memmap ->
        (bottom)."""
        return None


# ---------------------------------------------------------------------------
# DenseOperator — in-memory jax array (serial backend)
# ---------------------------------------------------------------------------

class DenseOperator(LinearOperator):
    """An in-memory ``(M, N)`` jax array behind the protocol.

    Expects the tall orientation (M >= N); the front door transposes
    wide inputs in and swaps the factors out (CSVD).  ``gram_chain``
    (and the sketch) contract the operand at ``sweep_dtype`` with fp32
    accumulation; ``matmat``/``extract`` stay fp32
    (``core/precision.py``).  On a TPU, where ``fused_chain_tiles``
    finds tiles, the chain is the Pallas kernel that reads each row
    tile of ``A`` once for both halves (``chain_passes = 1``);
    elsewhere, or for an iterate too wide for VMEM, it is XLA's two
    dots, two reads.  ``lagged_sync``: the convergence
    scalar is synced one iteration late so the driver's ``float()``
    lands after the next step is already dispatched — jax async dispatch
    keeps the device busy, at a bounded one-iteration overshoot.
    """

    backend = "dense"
    lagged_sync = True

    def __init__(self, X, *, sweep_dtype="float32"):
        super().__init__()
        self._X = jnp.asarray(X, jnp.float32)
        self.sweep_dtype = resolve_sweep_dtype(sweep_dtype).name
        devices = self._X.devices()
        self._platform = (next(iter(devices)).platform
                          if len(devices) == 1 else None)
        # the kernel exists for this operand if it does for the
        # narrowest iterate; gram_chain checks each iterate's width
        if self._chain_tiles(8) is not None:
            self.chain_passes = 1

    def _chain_tiles(self, l):
        return fused_chain_tiles(self._platform, self.shape, l,
                                 self.sweep_dtype)

    @property
    def shape(self):
        return self._X.shape

    def matmat(self, Q):
        self._count(1)
        return self._X @ Q

    def rmatmat(self, Y):
        self._count(1)
        return self._X.T @ Y

    def gram_chain(self, Q):
        tiles = self._chain_tiles(Q.shape[1])
        self._count(1 if tiles else 2)
        return _dense_chain(self._X, Q, sweep_dtype=self.sweep_dtype,
                            tiles=tiles)

    def range_sketch(self, l, seed):
        self._count(self.sketch_passes)
        # key built eagerly (exact for the full 64-bit seed space the
        # legacy key translation can produce); only the key array is traced
        return _dense_sketch(self._X, seed_to_key(seed),
                             l=l, sweep_dtype=self.sweep_dtype)

    def random_block(self, k, seed):
        return jax.random.normal(seed_to_key(seed),
                                 (self._X.shape[1], k), jnp.float32)

    def extract(self, Q):
        self._count(1)
        return _dense_extract(self._X, Q)

    def demote(self, cfg):
        # device OOM: pull A back to host and stream it block-by-block
        # (same math, same sweep dtype, H2D per block instead of
        # device-resident A)
        from repro.core.oom import HostBlockedMatrix
        A = np.asarray(jax.device_get(self._X), np.float32)
        host = HostBlockedMatrix(A, cfg.n_blocks,
                                 stage_dtype=self.sweep_dtype)
        return HostBlockedOperator(host)

    @property
    def bytes_per_pass(self):
        m, n = self._X.shape
        return m * n * jnp.dtype(self.sweep_dtype).itemsize


# ---------------------------------------------------------------------------
# ShardedOperator — row-sharded jax array over mesh axes
# ---------------------------------------------------------------------------

def _row_spec(axes):
    return P(axes if len(axes) > 1 else axes[0], None)


@functools.lru_cache(maxsize=None)
def sharded_gram_chain_fn(mesh, axes, sweep_dtype):
    """jitted ``(A, Q) -> psum(A_loc^T (A_loc Q))`` — the block step's
    fused sweep: ONE ``(n, k)`` collective advances all k ranks.  Cached
    per (mesh, axes, dtype) so repeated ``svd()`` calls reuse the
    compiled step; also lowered as-is by ``launch/svd_dryrun.py`` so the
    analyzed collective schedule can't drift from the driver."""
    spec = _row_spec(axes)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, P(None, None)),
                       out_specs=P(None, None))
    def gram_chain(A_loc, Q):
        mm, rmm = sweep_ops(A_loc.astype(jnp.float32), sweep_dtype)
        return jax.lax.psum(rmm(mm(Q)), axes)

    return jax.jit(gram_chain)


@functools.lru_cache(maxsize=None)
def sharded_block_step_fn(mesh, axes, sweep_dtype):
    """ONE driver block step on the sharded backend: the fused-psum gram
    chain composed with the shared QR orthonormalization — exactly the
    two jitted primitives ``core/svd.py::step`` dispatches per
    iteration.  ``launch/svd_dryrun.py`` lowers THIS function, so the
    analyzed collective schedule can't drift from the solver."""
    chain = sharded_gram_chain_fn(mesh, axes, sweep_dtype)

    def block_step(A, Q):
        return _orth(chain(A, Q))

    return jax.jit(block_step)


@functools.lru_cache(maxsize=None)
def sharded_sketch_fn(mesh, axes, l, sweep_dtype):
    """jitted ``(A, seed_arr) -> psum(A_loc^T Omega_loc)``: each shard
    sketches its own Gaussian row block (the flat shard index is folded
    into the key), so the ``(m, l)`` Omega is never resident anywhere."""
    spec = _row_spec(axes)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, P(None)),
                       out_specs=P(None, None))
    def sketch(A_loc, seed_arr):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed_arr[0])
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        okey = jax.random.fold_in(jax.random.fold_in(key, 1), idx)
        Om = jax.random.normal(okey, (A_loc.shape[0], l), jnp.float32)
        _, rmm = sweep_ops(A_loc.astype(jnp.float32), sweep_dtype)
        return jax.lax.psum(rmm(Om), axes)

    return jax.jit(sketch)


@functools.lru_cache(maxsize=None)
def sharded_matmat_fn(mesh, axes):
    spec = _row_spec(axes)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, P(None, None)), out_specs=spec)
    def matmat(A_loc, Q):
        return A_loc.astype(jnp.float32) @ Q

    return jax.jit(matmat)


@functools.lru_cache(maxsize=None)
def sharded_rmatmat_fn(mesh, axes):
    spec = _row_spec(axes)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec), out_specs=P(None, None))
    def rmatmat(A_loc, Y_loc):
        return jax.lax.psum(A_loc.astype(jnp.float32).T @ Y_loc, axes)

    return jax.jit(rmatmat)


@functools.lru_cache(maxsize=None)
def sharded_extract_fn(mesh, axes):
    """Rayleigh–Ritz through the psum'd ``(l, l)`` Gram of ``W = A Q`` —
    no distributed QR of a tall matrix is ever needed.  Returns the full
    l-width factors (U row-sharded, S and V replicated); the driver
    truncates to k."""
    spec = _row_spec(axes)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, P(None, None)),
                       out_specs=(spec, P(None), P(None, None)))
    def extract(A_loc, Q):
        W_loc = A_loc.astype(jnp.float32) @ Q          # (m_loc, l) sharded
        G = jax.lax.psum(W_loc.T @ W_loc, axes)        # (l, l) replicated
        lam, P_g = jnp.linalg.eigh(G)                  # ascending order
        lam, P_g = lam[::-1], P_g[:, ::-1]
        S = jnp.sqrt(jnp.clip(lam, 0.0))
        # Zero — don't 1/eps-blow-up — directions beyond the numerical
        # rank (lam ~ 0): their U columns are noise either way, but this
        # keeps every entry finite when k > rank(A).
        inv = jnp.where(S > 1e-6 * S[0], 1.0 / (S + 1e-30), 0.0)
        return (W_loc @ P_g) * inv[None, :], S, Q @ P_g

    return jax.jit(extract)


class ShardedOperator(LinearOperator):
    """A row-sharded jax array over named mesh axes (paper's N-GPU map).

    Every A-sized product is a ``shard_map`` whose only collective is one
    fused psum; QR/eigh run on replicated skinny blocks outside.  The
    two sweeps of ``gram_chain`` read the shard at ``sweep_dtype`` with
    fp32 accumulation — psum payloads are fp32 accumulator outputs, so
    per-chip HBM bytes halve under bf16 while collective bytes are
    unchanged.  Expects the tall orientation with ``m`` divisible by the
    product of the axis sizes.  ``lagged_sync``: the driver syncs the
    convergence scalar one iteration late, so the host never serializes
    collective steps against D2H latency (dispatch stays a step ahead;
    overshoot bounded at one iteration).
    """

    backend = "sharded"
    lagged_sync = True

    def __init__(self, A, mesh, axes=("data",), *, sweep_dtype="float32"):
        super().__init__()
        axes = tuple(axes)
        nshards = 1
        for a in axes:
            nshards *= mesh.shape[a]
        m, n = A.shape
        if m % nshards:
            raise ValueError(f"m={m} not divisible by shards={nshards}; "
                             "pad first")
        self.mesh, self.axes = mesh, axes
        self.n_shards = nshards
        self.sweep_dtype = resolve_sweep_dtype(sweep_dtype).name
        self._A = jax.device_put(
            A, NamedSharding(mesh, _row_spec(axes)))

    @property
    def shape(self):
        return self._A.shape

    def matmat(self, Q):
        self._count(1)
        return sharded_matmat_fn(self.mesh, self.axes)(self._A, Q)

    def rmatmat(self, Y):
        self._count(1)
        return sharded_rmatmat_fn(self.mesh, self.axes)(self._A, Y)

    def gram_chain(self, Q):
        self._count(self.chain_passes)
        return sharded_gram_chain_fn(
            self.mesh, self.axes, self.sweep_dtype)(self._A, Q)

    def range_sketch(self, l, seed):
        self._count(self.sketch_passes)
        return sharded_sketch_fn(self.mesh, self.axes, l, self.sweep_dtype)(
            self._A, jnp.array([seed & 0xFFFFFFFF], jnp.uint32))

    def random_block(self, k, seed):
        key = jax.random.fold_in(jax.random.PRNGKey(0),
                                 jnp.uint32(seed & 0xFFFFFFFF))
        return jax.random.normal(key, (self._A.shape[1], k), jnp.float32)

    def extract(self, Q):
        self._count(1)
        return sharded_extract_fn(self.mesh, self.axes)(self._A, Q)

    def from_host(self, W):
        # the iterate is replicated across the mesh (only A is sharded)
        return jax.device_put(jnp.asarray(W, jnp.float32),
                              NamedSharding(self.mesh, P(None, None)))

    def demote(self, cfg):
        # mesh OOM: gather the shards back to host and stream H2D on
        # one device — slower, but the solve finishes
        from repro.core.oom import HostBlockedMatrix
        A = np.asarray(jax.device_get(self._A), np.float32)
        host = HostBlockedMatrix(A, cfg.n_blocks,
                                 stage_dtype=self.sweep_dtype)
        return HostBlockedOperator(host)

    @property
    def fingerprint(self):
        return super().fingerprint + f":shards={self.n_shards}"

    @property
    def bytes_per_pass(self):
        m, n = self._A.shape
        return m * n * jnp.dtype(self.sweep_dtype).itemsize


# ---------------------------------------------------------------------------
# HostBlockedOperator — host-resident row blocks streamed H2D (degree-1)
# ---------------------------------------------------------------------------

class HostBlockedOperator(LinearOperator):
    """Wraps a ``HostBlockedMatrix`` (or an instrumented subclass).

    A "pass" is one full H2D stream of the host blocks — the paper's
    dominant degree-1 cost.  The fused ``gram_chain`` generates/copies
    each block ONCE for both sweep halves (``chain_passes = 1``), and
    the sketch's Omega row blocks are generated on the fly, never
    resident.  ``lagged_sync`` tells the driver to sync the convergence
    scalar one iteration late so ``float()`` never stalls the async H2D
    prefetch (overshoot bounded at one pass).  The sweep dtype is the
    wrapped matrix's ``stage_dtype`` (bf16 staging halves every H2D
    copy; device accumulation stays fp32).
    """

    backend = "hostblocked"
    chain_passes = 1
    lagged_sync = True

    def __init__(self, host):
        super().__init__()
        self._host = host
        self.sweep_dtype = jnp.dtype(host.stage_dtype).name

    @property
    def host(self):
        return self._host

    @property
    def shape(self):
        return (self._host.m, self._host.n)

    def matmat(self, Q):
        self._count(1)
        return self._host.matmat(Q)

    def rmatmat(self, Y):
        self._count(1)
        return self._host.rmatmat(Y)

    def gram_chain(self, Q):
        self._count(self.chain_passes)
        return self._host.gram_chain(Q)

    def range_sketch(self, l, seed):
        self._count(self.sketch_passes)
        from repro.core.oom import hostblock_sketch_step_fn
        host = self._host
        okey = jax.random.fold_in(seed_to_key(seed), 1)
        sd = host.stage_dtype
        acc = jnp.zeros((host.n, l), jnp.float32)
        step = hostblock_sketch_step_fn()   # cached: no per-call retrace
        nxt = host.block(0)
        for b in range(host.n_blocks):     # one pass; Omega never resident
            cur = nxt
            if b + 1 < host.n_blocks:      # prefetch next block (async H2D)
                with span("stage.pace"):   # block b-1 consumed
                    jax.block_until_ready(acc)
                nxt = host.block(b + 1)
            om_b = jax.random.normal(jax.random.fold_in(okey, b),
                                     (cur.shape[0], l), jnp.float32)
            acc = step(acc, cur, om_b.astype(sd))
        return acc

    def random_block(self, k, seed):
        return jax.random.normal(seed_to_key(seed),
                                 (self._host.n, k), jnp.float32)

    def reset_counters(self):
        self.reset_passes()
        reset = getattr(self._host, "reset_counters", None)
        if reset is not None:
            reset()

    def set_resilience(self, telemetry=None, retry_policy=None):
        # the staging hops live on the matrix, so the retry loop's
        # telemetry/policy must land there
        super().set_resilience(telemetry, retry_policy)
        self._host.telemetry = telemetry
        self._host.retry_policy = retry_policy

    def demote(self, cfg):
        """Host pressure: spill the staged blocks to a temp ``.npy``
        and re-wrap as the disk tier.  The spill is blockwise (nothing
        matrix-sized is ever resident) and the memmap keeps the same
        block plan, so the streamed FP accumulation order — and with it
        bitwise reproducibility — is unchanged.  The host cache budget
        is ``cfg.host_budget_bytes`` when set, else half the file, so
        the demoted tier actually holds less host memory."""
        import tempfile
        from repro.core.diskio import MemmapMatrix
        host = self._host
        fd, path = tempfile.mkstemp(suffix=".npy", prefix="repro_demoted_")
        os.close(fd)
        sd = np.dtype(host.stage_dtype)
        out = np.lib.format.open_memmap(path, mode="w+", dtype=sd,
                                        shape=(host.m, host.n))
        for b in range(host.n_blocks):
            lo, hi = host.plan.bounds(b)
            out[lo:hi] = host.host_block(b)
        out.flush()
        del out
        budget = cfg.host_budget_bytes or (host.m * host.n *
                                           sd.itemsize) // 2
        mm = MemmapMatrix(path, host.n_blocks, stage_dtype=sd.name,
                          host_budget_bytes=budget)
        op = MemmapOperator(mm)
        op.spill_path = path    # caller owns the temp file's lifetime
        return op

    @property
    def bytes_per_pass(self):
        return self._host.bytes_per_pass

    @property
    def bytes_moved(self):
        # every pass crosses the host tier (H2D copy of the staged
        # blocks) and is then read once from device memory
        moved = self.passes * self.bytes_per_pass
        return {"host": moved, "device": moved}


# ---------------------------------------------------------------------------
# MemmapOperator — disk-resident row blocks staged disk->host->device
# ---------------------------------------------------------------------------

class MemmapOperator(HostBlockedOperator):
    """Wraps a ``MemmapMatrix`` (``core/diskio.py``): the disk tier.

    Identical streaming/pass semantics to ``HostBlockedOperator`` (the
    matrix inherits every double-buffered fused sweep), plus the disk
    rung of the hierarchy: ``bytes_moved`` reports the matrix's ACTUAL
    tier counters, so a host cache large enough to hold the staged
    blocks shows one cold file read while a capped budget shows one
    disk read per pass.  ``stage_dtype="bfloat16"`` files halve both
    the disk and the PCIe bytes (the file stores 2-byte elements).
    """

    backend = "memmap"

    def demote(self, cfg):
        return None          # disk is the bottom of the ladder

    @property
    def bytes_moved(self):
        return self._host.bytes_moved


# ---------------------------------------------------------------------------
# SparseStreamOperator — procedural sparse (or duck-typed streamed) matrix
# ---------------------------------------------------------------------------

class SparseStreamOperator(LinearOperator):
    """Wraps a streamed host operator (``SyntheticSparseMatrix``,
    ``DenseStreamOperator``, or anything with their ``matmat``/
    ``rmatmat``/``gram_chain``/``range_sketch`` surface).

    A "pass" is one full stream of the nonzeros; ``gram_chain`` fuses
    both sweep halves onto one generated stream (``chain_passes = 1``).
    The streamed sweeps round operands to ``sweep_dtype`` with fp32
    accumulation (numpy emulation of the device policy); the extraction
    pass stays fp32.
    """

    backend = "sparsestream"
    chain_passes = 1

    def __init__(self, sp, *, block_rows=1 << 16, sweep_dtype="float32"):
        super().__init__()
        self._sp = sp
        self._block_rows = block_rows
        self.sweep_dtype = resolve_sweep_dtype(sweep_dtype).name

    @property
    def shape(self):
        return (self._sp.m, self._sp.n)

    @property
    def dtype(self):
        return np.float32

    def matmat(self, Q):
        self._count(1)
        return self._sp.matmat(np.asarray(Q, np.float32), self._block_rows)

    def rmatmat(self, Y):
        self._count(1)
        return self._sp.rmatmat(np.asarray(Y, np.float32), self._block_rows)

    def gram_chain(self, Q):
        self._count(self.chain_passes)
        return self._sp.gram_chain(np.asarray(Q, np.float32),
                                   self._block_rows,
                                   dtype=self.sweep_dtype)

    def range_sketch(self, l, seed):
        self._count(self.sketch_passes)
        return self._sp.range_sketch(l, seed=seed,
                                     block_rows=self._block_rows,
                                     dtype=self.sweep_dtype)

    def random_block(self, k, seed):
        rng = np.random.default_rng(seed)
        return rng.standard_normal((self._sp.n, k)).astype(np.float32)

    def orth(self, X):
        return np.linalg.qr(X)[0].astype(np.float32)

    def subspace_gap(self, Q, Qn):
        return float(Q.shape[1] - np.sum((Q.T @ Qn) ** 2))

    def extract(self, Q):
        W = self.matmat(Q)                 # fp32 extraction pass (counted)
        U, S, V = rayleigh_ritz_from_W(jnp.asarray(W), jnp.asarray(Q))
        return np.asarray(U), np.asarray(S), np.asarray(V)

    def to_host(self, X):
        return np.asarray(X, np.float32)   # already host-resident numpy

    def from_host(self, W):
        return np.asarray(W, np.float32)

    @property
    def bytes_per_pass(self):
        sp = self._sp
        elems = getattr(sp, "nnz", sp.m * sp.n)
        return elems * np.dtype(self.sweep_dtype).itemsize

    @property
    def bytes_moved(self):
        # the nonzero stream is generated/read and consumed on the host
        return {"host": self.passes * self.bytes_per_pass}
