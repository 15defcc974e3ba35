"""Disk tier for the out-of-core path: memmap-backed blocked matrices.

The paper's memory hierarchy is disk -> host -> device; the host tier
(``core/oom.py::HostBlockedMatrix``) assumes the whole matrix sits in
host RAM.  This module adds the bottom rung: ``MemmapMatrix`` keeps the
matrix in a file (``np.memmap``) and stages row blocks disk -> host ->
device on demand, so matrices larger than host RAM stream through the
same fused block sweeps — the out-of-core shape of Demchik et al.
(arXiv:1907.06470) and Lu et al. (arXiv:1706.07191), with the paper's
double-buffered prefetch reused for BOTH hops:

* ``MemmapMatrix`` subclasses ``HostBlockedMatrix`` and overrides only
  the staging hop (``host_block``): a block is read from the memmap,
  cast to ``stage_dtype``, and (optionally) kept in a host cache bounded
  by ``host_budget_bytes``.  Every streamed op (``matmat``/``rmatmat``/
  ``gram_chain``/``gram``/``matvec``) is inherited, so the prefetch of
  block ``b+1`` issues the disk read AND the async H2D copy while block
  ``b`` computes.
* ``stage_to_disk`` writes an array to a ``.npy`` file AT the staging
  dtype, block by block (nothing matrix-sized is ever resident), so
  ``stage_dtype="bfloat16"`` halves the bytes of BOTH remaining hops:
  each disk read and each PCIe (H2D) copy moves 2 bytes/element.
* per-tier accounting: the matrix counts the actual bytes each tier
  moved (``disk_bytes`` read from the file, ``h2d_bytes`` staged to
  device) plus ``fetches``/``passes`` in the ``CountingHostMatrix``
  style — the ground truth the reported ``SVDResult.bytes_moved``
  breakdown is asserted against in the tests.

Host-budget semantics (``host_budget_bytes``):

* ``0`` (default) — unbounded: staged blocks are cached, so after the
  first cold pass the solve runs at host speed (disk bytes = one read
  of the file).
* ``> 0`` — the staged-block cache (LRU) never exceeds the budget.  A
  cyclic block sweep over a working set larger than the budget misses
  on every fetch, so disk bytes = one file read PER pass — exactly the
  analytic model the accounting tests pin down.
"""
from __future__ import annotations

import collections
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import InputError
from repro.core.faults import fault_hook, retry_io
from repro.core.oom import HostBlockedMatrix
from repro.core.partition import make_batch_plan
from repro.core.precision import resolve_sweep_dtype

__all__ = ["MemmapMatrix", "stage_to_disk", "open_matrix_memmap"]

#: rows staged per write when spilling an array to disk (bounds host
#: memory during staging, not during the solve)
_STAGE_ROWS = 1 << 14


def stage_to_disk(A, path, *, dtype="float32") -> str:
    """Write ``A`` to ``path`` (``.npy``) at the staging dtype, blockwise.

    The file IS the staged representation: ``dtype="bfloat16"`` stores
    2 bytes/element, so every later disk read (and the H2D copy of the
    already-narrow block) moves half the bytes.  Rows are written in
    bounded strips so staging itself never materializes the full array.
    Returns ``path``.
    """
    sd = np.dtype(resolve_sweep_dtype(dtype))
    m, n = A.shape
    out = np.lib.format.open_memmap(os.fspath(path), mode="w+",
                                    dtype=sd, shape=(m, n))
    for lo in range(0, m, _STAGE_ROWS):
        hi = min(lo + _STAGE_ROWS, m)
        out[lo:hi] = np.asarray(A[lo:hi], np.float32).astype(sd)
    out.flush()
    del out
    return os.fspath(path)


def open_matrix_memmap(path) -> np.ndarray:
    """Memory-map a ``.npy`` matrix written by ``stage_to_disk``/np.save.

    numpy round-trips the ml_dtypes bfloat16 descr as a raw 2-byte void
    dtype under ``mmap_mode``; such files are viewed back as bf16 (the
    bytes are identical), so bf16-staged files load transparently.

    A missing, truncated, or non-``.npy`` file raises ``InputError``
    (not a raw numpy traceback) with the path in the message.
    """
    p = os.fspath(path)
    try:
        arr = np.load(p, mmap_mode="r")
    except (OSError, ValueError, EOFError) as e:
        raise InputError(
            f"{p!r} is not a readable .npy matrix ({type(e).__name__}: "
            f"{e}); re-stage it with repro.core.stage_to_disk() or point "
            f"svd() at an intact file") from e
    if not hasattr(arr, "ndim") or arr.ndim != 2:
        raise InputError(
            f"{p!r} does not hold a 2-D matrix (got "
            f"ndim={getattr(arr, 'ndim', None)}); svd() needs an (m, n) "
            f"array on disk")
    if arr.dtype == np.dtype("V2"):
        arr = arr.view(np.dtype(jnp.bfloat16))
    return arr


class MemmapMatrix(HostBlockedMatrix):
    """Row-blocked matrix living on DISK, staged disk->host->device.

    ``source`` is a path to a ``.npy`` file, an ``np.memmap``, or any
    array-like whose row slices are cheap views (a transposed memmap for
    the CSVD orientation works too).  Blocks are read on demand; the
    host never holds more than ``host_budget_bytes`` of staged blocks
    (plus the one block in flight), so the solve's host footprint is
    bounded no matter how large the file is.

    If the file is already stored at ``stage_dtype`` (``stage_to_disk``)
    the staging cast is a no-op and disk bytes == H2D bytes; a wider
    file (e.g. fp32 on disk, bf16 staging) is narrowed at the host hop,
    so only the disk read moves the wide bytes.

    Tier counters (all in bytes, monotonic over the matrix's lifetime):
    ``disk_bytes`` read from the memmap, ``h2d_bytes`` copied host->
    device; ``fetches``/``passes`` count H2D block fetches exactly like
    ``CountingHostMatrix``; ``peak_host_bytes`` is the high-water mark
    of the staged-block cache.
    """

    def __init__(self, source, n_blocks: int, stage_dtype="float32",
                 host_budget_bytes: int = 0):
        if isinstance(source, (str, os.PathLike)):
            source = open_matrix_memmap(source)
        if source.ndim != 2:
            raise ValueError(f"expected a 2-D matrix, got shape "
                             f"{source.shape}")
        if host_budget_bytes < 0:
            raise ValueError("host_budget_bytes must be >= 0 "
                             "(0 = unbounded)")
        # deliberately NOT super().__init__: the parent stages every
        # block into host RAM eagerly — the exact thing the disk tier
        # exists to avoid.
        self._mm = source
        self.m, self.n = source.shape
        self.stage_dtype = resolve_sweep_dtype(stage_dtype)
        self.plan = make_batch_plan(self.m, n_blocks, collinear=True)
        self.host_budget_bytes = int(host_budget_bytes)
        self._cache: collections.OrderedDict[int, np.ndarray] = \
            collections.OrderedDict()
        self._cache_bytes = 0
        self.disk_bytes = 0
        self.h2d_bytes = 0
        self.fetches = 0
        self.peak_host_bytes = 0
        # resilience plumbing, installed per-solve by the driver via
        # LinearOperator.set_resilience (None = defaults, no telemetry)
        self.telemetry = None
        self.retry_policy = None

    @property
    def file_dtype(self) -> np.dtype:
        return np.dtype(self._mm.dtype)

    @property
    def disk_bytes_per_pass(self) -> int:
        """File bytes one cold (uncached) full stream reads from disk."""
        return self.m * self.n * self.file_dtype.itemsize

    @property
    def passes(self) -> float:
        """H2D block fetches / n_blocks — the CountingHostMatrix unit."""
        return self.fetches / self.n_blocks

    @property
    def bytes_moved(self) -> dict[str, int]:
        """Actual bytes each tier moved so far: the per-tier breakdown
        ``SVDResult.bytes_moved`` reports (device reads the staged
        block it was handed, so the device tier equals the H2D tier)."""
        return {"disk": self.disk_bytes, "host": self.h2d_bytes,
                "device": self.h2d_bytes}

    def reset_counters(self):
        """Zero the tier counters (NOT the staged-block cache) so the
        driver's per-solve delta accounting starts clean; a warm cache
        legitimately shows as fewer disk bytes for the next solve."""
        self.disk_bytes = 0
        self.h2d_bytes = 0
        self.fetches = 0

    def host_block(self, b: int) -> np.ndarray:
        blk = self._cache.get(b)
        if blk is not None:
            self._cache.move_to_end(b)
            return blk
        lo, hi = self.plan.bounds(b)

        def _read():
            # the reliability-critical staging hop: a transient OSError
            # here (EIO, NFS hiccup, injected fault) is retried under
            # the driver's backoff policy, not surfaced to the solve
            fault_hook("disk_read", self.telemetry)
            return np.asarray(self._mm[lo:hi])     # the disk read

        raw = retry_io(_read, site="disk_read", policy=self.retry_policy,
                       telemetry=self.telemetry)
        self.disk_bytes += (hi - lo) * self.n * self.file_dtype.itemsize
        if raw.dtype == self.stage_dtype:
            blk = np.ascontiguousarray(raw)
        else:
            blk = np.ascontiguousarray(
                np.asarray(raw, dtype=np.float32), dtype=self.stage_dtype)
        budget = self.host_budget_bytes
        if budget == 0 or blk.nbytes <= budget:
            while (budget and self._cache
                   and self._cache_bytes + blk.nbytes > budget):
                _, old = self._cache.popitem(last=False)   # LRU evict
                self._cache_bytes -= old.nbytes
            self._cache[b] = blk
            self._cache_bytes += blk.nbytes
            self.peak_host_bytes = max(self.peak_host_bytes,
                                       self._cache_bytes)
        return blk

    def block(self, b: int) -> jax.Array:
        dev = super().block(b)
        self.fetches += 1
        self.h2d_bytes += dev.nbytes
        return dev
