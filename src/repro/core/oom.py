"""Out-of-memory (degree-0/1) blocked computation (paper §III-IV, Alg 3).

Device memory is bounded by streaming A through in blocks:

* ``blocked_gram``       — ``B = sum_b A_b^T A_b`` over row blocks via
  ``lax.scan``; peak live memory is one block + the accumulator, which is
  the TPU analogue of the paper's batched Gram with H2D copy per batch.
  XLA double-buffers the scan body, so the *next* block's loads overlap the
  current block's MXU work — the role the CUDA stream queue plays on GPU.
* ``tiled_gram``         — the paper's Alg-3 task structure: the local block
  is split column-wise into ``n_b`` batches and only upper-triangle tiles
  ``B_ij = A_i^T A_j`` (i <= j) are computed, the mirror filled by
  transposition (Fig 2c's reduced task count).
* ``blocked_deflated_matvec`` — the Alg-4 chain evaluated block-by-block so
  neither the residual, the Gram, nor even a full dense copy of ``A`` needs
  to be resident.
* ``_oom_deflation``     — rank-one deflation driver on the blocked
  operator (paper Alg 1+4, ``method="gramfree"``); the block subspace
  iteration runs through the shared driver (``repro.core.svd`` over
  ``core/operator.py::HostBlockedOperator``) — no copy of it lives
  here.  ``oom_tsvd`` is the deprecated back-compat shim.

Host↔device staging for true degree-1 problems is in ``HostBlockedMatrix``:
blocks live in host (numpy) memory and are ``device_put`` one at a time —
the JAX equivalent of the paper's H2D batch pipeline.

Pass/memory trade-off of the two strategies (the H2D copy is the dominant
cost at degree-1 scale, so "passes over A" is the unit that matters):

* deflation — device memory ``O(block + (m + n) k)``; data movement
  ``sum_l (2 iters_l + 1)`` full passes over ``A`` (two sweeps per power
  step per rank: forward mat-vec + fused reverse sweep).
* block     — device memory ``O(block + (m + n) k)`` as well (the iterate
  block ``(n, k)`` and one ``(rows_b, k)`` product tile), but each
  iteration streams every host block ONCE against all k vectors via the
  fused ``A_b^T (A_b Q)`` chain — k× less H2D traffic per extracted rank,
  ``iters + 2`` passes total.  Preferred whenever k > a few.

``warmup_q >= 1`` (block only) prepends the randomized range-finder warm
start: one streamed sketch pass ``A^T Omega`` (``Omega`` row blocks are
generated on the fly, never resident) plus ``q`` fused ``gram_chain``
refinement passes, turning ~10-15 cold subspace iterations into 1-2 for
spectra with a decaying tail.

``sweep_dtype="bfloat16"`` (block only) applies the mixed-precision
policy (``core/precision.py``) at the layer that matters most here:
``HostBlockedMatrix`` *stages* the host blocks at 2 bytes/element, so
every H2D batch copy — the paper's dominant degree-1 latency — moves
half the bytes, while on-device accumulation, QR, and Rayleigh–Ritz
stay fp32.

Both strategies report ``iters`` and ``passes_over_A`` in ``OOMResult``.
A pass is ONE full H2D stream of the host blocks (the fused chain
generates/copies each block once), so block costs
``[1 + q if warm] + iters + 1`` and deflation ``sum_l (2 iters_l + 1)``
— exactly what an instrumented ``HostBlockedMatrix`` counts (asserted in
the tests).  The count is dtype-independent: bf16 staging halves
``bytes_per_pass``, never the number of passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SVDConfig, SVDResult, seed_to_key
from repro.core.faults import fault_hook, retry_io
from repro.core.operator import host_sync_scalar
from repro.core.precision import resolve_sweep_dtype
from repro.core.partition import BatchPlan, make_batch_plan, symmetric_tasks
from repro.core.spans import span


def _f32dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` with fp32 accumulation regardless of operand dtype.

    For fp32 operands this is the plain dot (bit-stable with the
    pre-policy code); for bf16-staged blocks the MXU reads 2-byte
    operands and accumulates fp32 (``core/precision.py``).
    """
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return a @ b
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Per-block jitted step functions (module-level, lru-cached)
#
# jax's compile cache is keyed on callable IDENTITY: a `jax.jit(lambda ...)`
# built inside a method is a fresh callable — and a fresh retrace+recompile
# — on every call.  These builders return the ONE cached jitted step per
# signature, shared by every HostBlockedMatrix instance; they are also the
# functions `repro.analysis` traces, so the statically checked per-block
# schedule is exactly what the streamed loops dispatch.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def hostblock_gram_step_fn():
    """``acc + blk^T blk`` — one block of the streamed Gram."""
    def hostblock_gram_step(acc, blk):
        return acc + _f32dot(blk.T, blk)
    return jax.jit(hostblock_gram_step)


@functools.lru_cache(maxsize=None)
def hostblock_matvec_fn():
    """``blk @ v`` — one block of the streamed mat-vec."""
    def hostblock_matvec(blk, v):
        return _f32dot(blk, v)
    return jax.jit(hostblock_matvec)


@functools.lru_cache(maxsize=None)
def hostblock_matmat_fn():
    """``blk @ Q`` — one block of the streamed extraction pass."""
    def hostblock_matmat(blk, Q):
        return _f32dot(blk, Q)
    return jax.jit(hostblock_matmat)


@functools.lru_cache(maxsize=None)
def hostblock_rmatmat_step_fn():
    """``acc + blk^T y_b`` — one block of the streamed ``A^T Y``."""
    def hostblock_rmatmat_step(acc, blk, yb):
        return acc + _f32dot(blk.T, yb)
    return jax.jit(hostblock_rmatmat_step)


@functools.lru_cache(maxsize=None)
def hostblock_chain_step_fn(stage_dtype: str):
    """``acc + blk^T (blk Q)`` — one block of the FUSED gram chain, the
    hot loop's step: the block is read once for both sweep halves.
    Under bf16 staging both sweep operands are narrow (``Q`` and the
    intermediate cast down) with fp32 accumulation; fp32 staging keeps
    the plain dot (bit-stable with the pre-policy code)."""
    sd = jnp.dtype(stage_dtype)
    if sd == jnp.float32:
        def _step(acc, blk, Q):
            return acc + blk.T @ (blk @ Q)
    else:
        def _step(acc, blk, Q):
            y = _f32dot(blk, Q.astype(sd))
            return acc + _f32dot(blk.T, y.astype(sd))
    return jax.jit(_step)


@functools.lru_cache(maxsize=None)
def hostblock_sketch_step_fn():
    """``acc + blk^T om_b`` — one block of the streamed range sketch
    (Omega row blocks generated on the fly, never resident)."""
    def hostblock_sketch_step(acc, blk, om):
        return acc + _f32dot(blk.T, om)
    return jax.jit(hostblock_sketch_step)


@functools.lru_cache(maxsize=None)
def hostblock_deflate_step_fn():
    """``acc + blk^T (xv_b - u_b svtv)`` — one block of the fused Alg-4
    reverse sweep (``svtv`` passed as an argument, not closed over, so
    the compiled step is reused across deflation iterations)."""
    def hostblock_deflate_step(acc, blk, xvb, ub, svtv):
        return acc + blk.T @ (xvb - ub @ svtv)
    return jax.jit(hostblock_deflate_step)


# ---------------------------------------------------------------------------
# Blocked Gram (dense path)
# ---------------------------------------------------------------------------

def blocked_gram(blocks: jax.Array) -> jax.Array:
    """``B = sum_b blocks[b].T @ blocks[b]``; blocks: (n_b, rows_b, n).

    ``lax.scan`` keeps exactly one block live; the accumulator is (n, n).
    """

    def step(acc, blk):
        blk32 = blk.astype(jnp.float32)
        return acc + blk32.T @ blk32, None

    n = blocks.shape[-1]
    acc0 = jnp.zeros((n, n), jnp.float32)
    B, _ = jax.lax.scan(step, acc0, blocks)
    return B


def tiled_gram(A: jax.Array, n_batches: int) -> jax.Array:
    """Paper Alg 3 tile structure: column batches, symmetric-task trick.

    ``A (m x n)`` is split into ``n_b`` column batches ``A_j``; tiles
    ``B_ij = A_i^T A_j`` are computed for ``i <= j`` only and mirrored.
    Used to validate the Pallas gram kernel's task enumeration and as the
    jit-able reference for the OOM benchmarks.
    """
    m, n = A.shape
    plan = make_batch_plan(n, n_batches)
    bs = plan.batch_size
    n_pad = plan.n_batches * bs
    Ap = jnp.pad(A, ((0, 0), (0, n_pad - n))).astype(jnp.float32)
    nb = plan.n_batches

    B = jnp.zeros((n_pad, n_pad), jnp.float32)
    # Static task list (upper triangle) — unrolled; nb is small by design.
    for (i, j) in symmetric_tasks(nb):
        Ai = jax.lax.dynamic_slice(Ap, (0, i * bs), (m, bs))
        Aj = jax.lax.dynamic_slice(Ap, (0, j * bs), (m, bs))
        Bij = Ai.T @ Aj
        B = jax.lax.dynamic_update_slice(B, Bij, (i * bs, j * bs))
        if i != j:
            B = jax.lax.dynamic_update_slice(B, Bij.T, (j * bs, i * bs))
    return B[:n, :n]


# ---------------------------------------------------------------------------
# Blocked deflated mat-vec chain (sparse / gram-free path, Alg 4)
# ---------------------------------------------------------------------------

def blocked_deflated_matvec(
    blocks: jax.Array,   # (n_b, rows_b, n)  row blocks of A
    U_blocks: jax.Array, # (n_b, rows_b, k)  matching row blocks of U
    S: jax.Array,        # (k,)
    V: jax.Array,        # (n, k)            replicated
    v: jax.Array,        # (n,)
) -> jax.Array:
    """One Alg-4 step over row blocks: ``v1 = X'^T X' v`` without residual.

    Per block ``b``:  ``(Xv)_b = A_b v`` and the *fused* partial
    ``A_b^T ((Xv)_b - U_b (S * V^T v))`` accumulate into the output, while
    ``U_b^T (Xv)_b`` accumulates the k-vector needed for the V-side terms.
    This fuses the paper's lines 3-8 and 14-16 into one sweep over A —
    a single pass of data movement instead of two (recorded as a
    beyond-paper optimization; the faithful two-sweep variant lives in
    ``dist_svd.deflated_matvec_faithful``).
    """
    Vtv = V.T @ v                      # (k,)  replicated, cheap
    SVtv = S * Vtv                     # (k,)

    def step(carry, xs):
        acc_n, acc_k = carry
        A_b, U_b = xs
        A_b = A_b.astype(jnp.float32)
        Xv_b = A_b @ v                 # (rows_b,)
        corr = U_b @ SVtv              # (rows_b,)   U S V^T v  (block rows)
        acc_n = acc_n + A_b.T @ (Xv_b - corr)   # fused t1 - t3 partial
        acc_k = acc_k + U_b.T @ Xv_b            # U^T X v partial
        return (acc_n, acc_k), None

    n = blocks.shape[-1]
    k = S.shape[0]
    (t13, UtXv), _ = jax.lax.scan(
        step, (jnp.zeros((n,), jnp.float32), jnp.zeros((k,), jnp.float32)),
        (blocks, U_blocks))
    t2 = V @ (S * UtXv)                # V S U^T X v
    t4 = V @ (S * S * Vtv)             # V S^2 V^T v
    return t13 - t2 + t4


# ---------------------------------------------------------------------------
# Host-resident blocked matrix (true degree-1 OOM staging)
# ---------------------------------------------------------------------------

class HostBlockedMatrix:
    """Row-blocked matrix living in host memory, streamed block-by-block.

    The paper's degree-1 scenario: ``A`` does not fit on device; blocks are
    H2D-copied on demand. ``device_put`` of block ``b+1`` is issued while
    block ``b`` computes (JAX dispatch is async), which is the TPU-side
    analogue of the stream-queue overlap.  Every streamed loop waits for
    the step that consumed block ``b-1`` before it issues block ``b+1``'s
    copy, so at most three blocks are live on the device: unpaced, async
    dispatch enqueues the whole pass at once (a 20 GiB pass in 640 MiB
    blocks held 16.3 GB of a v5e's 16.9 GB).

    ``stage_dtype="bfloat16"`` stages the host blocks at 2 bytes/element,
    so every H2D copy — the paper's dominant degree-1 cost — moves HALF
    the bytes; on-device accumulation stays fp32 (``_f32dot``).  The
    rounding happens once at staging time; all streamed ops then read
    the narrow copy.

    The staging hop is the ONE extension point: ``host_block(b)`` returns
    the staged host-side (numpy) copy of block ``b``; ``block(b)`` puts
    it on device.  The disk tier (``core/diskio.py::MemmapMatrix``)
    overrides ``host_block`` to pull the block from an ``np.memmap``
    under a bounded host budget, and inherits every double-buffered
    streamed op below — the prefetch of block ``b+1`` then overlaps BOTH
    hops (disk->host read and host->device copy) with block ``b``'s
    compute.
    """

    def __init__(self, A_host: np.ndarray, n_blocks: int,
                 stage_dtype="float32"):
        self.m, self.n = A_host.shape
        self.stage_dtype = resolve_sweep_dtype(stage_dtype)
        self.plan = make_batch_plan(self.m, n_blocks, collinear=True)
        self._blocks = [
            np.ascontiguousarray(  # ml_dtypes-backed bf16 when staged narrow
                np.asarray(A_host[lo:hi], dtype=np.float32),
                dtype=self.stage_dtype)
            for lo, hi in (self.plan.bounds(b) for b in range(self.plan.n_batches))
        ]
        # resilience plumbing, installed per-solve by the driver via
        # LinearOperator.set_resilience (None = defaults, no telemetry)
        self.telemetry = None
        self.retry_policy = None

    @property
    def n_blocks(self) -> int:
        return self.plan.n_batches

    @property
    def bytes_per_pass(self) -> int:
        """H2D bytes one full stream of the host blocks moves."""
        return self.m * self.n * self.stage_dtype.itemsize

    def host_block(self, b: int) -> np.ndarray:
        """Staged host-side copy of block ``b`` (already at stage_dtype)."""
        return self._blocks[b]

    def block(self, b: int) -> jax.Array:
        blk = self.host_block(b)

        def _put():
            fault_hook("h2d", self.telemetry)
            with span("stage.h2d"):
                return jnp.asarray(blk)            # the H2D copy

        return retry_io(_put, site="h2d", policy=self.retry_policy,
                        telemetry=self.telemetry)

    def gram(self) -> jax.Array:
        """Streamed ``A^T A`` with bounded device memory."""
        acc = jnp.zeros((self.n, self.n), jnp.float32)
        step = hostblock_gram_step_fn()    # cached: no per-call retrace
        # Prefetch pipeline: issue H2D for the next block while current
        # computes (async dispatch) — the q_s=2 double-buffer case.
        nxt = self.block(0)
        for b in range(self.n_blocks):
            cur = nxt
            if b + 1 < self.n_blocks:
                with span("stage.pace"):   # block b-1 consumed
                    jax.block_until_ready(acc)
                nxt = self.block(b + 1)
            acc = step(acc, cur)
        return acc

    def matvec(self, v: jax.Array) -> jax.Array:
        """``A @ v`` streamed; returns (m,).  Double-buffered like
        ``gram``/``gram_chain`` so the next block's H2D overlaps the
        current block's compute."""
        outs = []
        mv = hostblock_matvec_fn()         # cached: no per-call retrace
        nxt = self.block(0)
        for b in range(self.n_blocks):
            cur = nxt
            if b + 1 < self.n_blocks:  # prefetch next block (async H2D)
                with span("stage.pace"):   # block b-1 consumed
                    jax.block_until_ready(outs[-1:])
                nxt = self.block(b + 1)
            outs.append(mv(cur, v))
        return jnp.concatenate(outs)

    def matmat(self, Q: jax.Array) -> jax.Array:
        """``A @ Q`` streamed; Q: (n, k) -> (m, k).  One pass over A,
        double-buffered — this is the Rayleigh–Ritz extraction pass of
        the block driver, so serializing H2D against compute here would
        stall the exact pipeline the iterate just kept busy.  ``Q`` stays
        fp32 (extraction accuracy); only ``A``'s staging is narrow."""
        outs = []
        mm = hostblock_matmat_fn()         # cached: no per-call retrace
        nxt = self.block(0)
        for b in range(self.n_blocks):
            cur = nxt
            if b + 1 < self.n_blocks:  # prefetch next block (async H2D)
                with span("stage.pace"):   # block b-1 consumed
                    jax.block_until_ready(outs[-1:])
                nxt = self.block(b + 1)
            outs.append(mm(cur, Q))
        return jnp.concatenate(outs)

    def rmatmat(self, Y: jax.Array) -> jax.Array:
        """``A.T @ Y`` streamed; Y: (m, k) -> (n, k).  One pass over A,
        double-buffered like the other streamed ops.  ``Y`` stays fp32;
        only ``A``'s staging is narrow."""
        acc = jnp.zeros((self.n, Y.shape[1]), jnp.float32)
        step = hostblock_rmatmat_step_fn() # cached: no per-call retrace
        nxt = self.block(0)
        for b in range(self.n_blocks):
            lo, hi = self.plan.bounds(b)
            cur = nxt
            if b + 1 < self.n_blocks:  # prefetch next block (async H2D)
                with span("stage.pace"):   # block b-1 consumed
                    jax.block_until_ready(acc)
                nxt = self.block(b + 1)
            acc = step(acc, cur, Y[lo:hi])
        return acc

    def gram_chain(self, Q: jax.Array) -> jax.Array:
        """``A^T (A Q)`` in ONE streamed pass: each host block is H2D-copied
        once and multiplied against all k columns — the block method's
        k-fold H2D saving over per-rank deflation loops.  Under bf16
        staging both sweep operands are narrow (``Q`` and the
        intermediate are cast down) with fp32 accumulation."""
        acc = jnp.zeros((self.n, Q.shape[1]), jnp.float32)
        step = hostblock_chain_step_fn(self.stage_dtype.name)
        nxt = self.block(0)
        for b in range(self.n_blocks):
            cur = nxt
            if b + 1 < self.n_blocks:  # prefetch next block (async H2D)
                with span("stage.pace"):   # block b-1 consumed
                    jax.block_until_ready(acc)
                nxt = self.block(b + 1)
            acc = step(acc, cur, Q)
        return acc

    def rmatvec_minus_correction(self, Xv_blocks: list[jax.Array],
                                 U_blocks: list[jax.Array],
                                 SVtv: jax.Array) -> jax.Array:
        """``sum_b A_b^T (Xv_b - U_b @ SVtv)`` streamed (fused Alg-4 sweep)."""
        acc = jnp.zeros((self.n,), jnp.float32)
        step = hostblock_deflate_step_fn() # cached: no per-call retrace
        for b in range(self.n_blocks):
            acc = step(acc, self.block(b), Xv_blocks[b], U_blocks[b], SVtv)
        return acc


class CountingHostMatrix(HostBlockedMatrix):
    """Instrumented ``HostBlockedMatrix``: counts host-block fetches.

    ``fetches / n_blocks`` is the number of full passes over ``A`` the
    driver actually streamed — the ground truth the analytic
    ``passes_over_A`` accounting is asserted against in the tests and in
    ``benchmarks/block_vs_deflation.py``.
    """

    def __init__(self, A_host, n_blocks, stage_dtype="float32"):
        super().__init__(A_host, n_blocks, stage_dtype=stage_dtype)
        self.fetches = 0

    def block(self, b):
        self.fetches += 1
        return super().block(b)

    @property
    def passes(self) -> float:
        return self.fetches / self.n_blocks

    def reset_counters(self):
        self.fetches = 0


# ---------------------------------------------------------------------------
# OOM deflation engine (blocked operator, single device)
# ---------------------------------------------------------------------------

#: Back-compat alias — the per-backend result NamedTuples were unified.
OOMResult = SVDResult


# How often the DEFLATION inner loop fetches the device-side convergence
# flag.  ``bool(done)`` forces a host sync, stalling the async-dispatch
# H2D prefetch pipeline; checking every few steps keeps dispatch running
# ahead at the cost of at most CHECK_EVERY - 1 extra (cheap, vector-
# sized) iterations.  The BLOCK driver (``core/svd.py``) instead uses a
# lag-one check: its iterations are full passes over A, so even one
# skipped check is expensive there.
CONVERGENCE_CHECK_EVERY = 4


def _oom_deflation(op: HostBlockedMatrix, k: int, *, eps, max_iters,
                   force_iters, seed):
    """Alg-4 rank-one deflation on the streamed host-resident operator.

    One fused sweep over the host blocks per power step (2 streams per
    step counting the u-recovery structure — see the pass accounting).
    Expects the tall orientation.  Returns ``(U, S, V, iters, passes)``.
    """
    m, n = op.m, op.n
    key = seed_to_key(seed)

    bounds = [op.plan.bounds(b) for b in range(op.n_blocks)]

    U = jnp.zeros((m, k), jnp.float32)
    S = jnp.zeros((k,), jnp.float32)
    V = jnp.zeros((n, k), jnp.float32)
    iters_out = np.zeros((k,), np.int32)
    passes = 0

    norm = lambda x: jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))

    for l in range(k):
        key, sub = jax.random.split(key)
        v = jax.random.normal(sub, (n,), jnp.float32)
        v = v / norm(v)
        it = 0
        for it in range(1, max_iters + 1):
            # One fused Alg-4 sweep over host-resident blocks.
            Vtv = V.T @ v
            SVtv = S * Vtv
            Xv_blocks = []
            UtXv = jnp.zeros((k,), jnp.float32)
            for b, (lo, hi) in enumerate(bounds):
                blk = op.block(b)
                xvb = blk @ v
                Xv_blocks.append(xvb)
                UtXv = UtXv + U[lo:hi].T @ xvb
            t13 = op.rmatvec_minus_correction(
                Xv_blocks, [U[lo:hi] for lo, hi in bounds], SVtv)
            v1 = t13 - V @ (S * UtXv) + V @ (S * S * Vtv)
            v1 = v1 / (norm(v1) + 1e-30)
            done = jnp.abs(jnp.vdot(v, v1)) >= 1.0 - eps
            v = v1
            # Fetch `done` on-host only every few steps: each bool() is a
            # device sync that would stall the H2D prefetch pipeline.
            if force_iters:
                continue
            if it % CONVERGENCE_CHECK_EVERY == 0 or it == max_iters:
                if host_sync_scalar(done):   # sanctioned periodic sync
                    break
        iters_out[l] = it
        passes += 2 * it + 1       # 2 streams per power step + u recovery
        # u = (A - U S V^T) v, streamed.
        SVtv = S * (V.T @ v)
        u_parts = []
        for b, (lo, hi) in enumerate(bounds):
            u_parts.append(op.block(b) @ v - U[lo:hi] @ SVtv)
        u = jnp.concatenate(u_parts)
        sigma = norm(u)
        u = u / (sigma + 1e-30)
        U = U.at[:, l].set(u)
        S = S.at[l].set(sigma)
        V = V.at[:, l].set(v)

    return U, S, V, iters_out, passes


# ---------------------------------------------------------------------------
# Deprecated back-compat shim
# ---------------------------------------------------------------------------

def oom_tsvd(
    A_host: np.ndarray,
    k: int,
    *,
    n_blocks: int = 4,
    eps: float = 1e-6,
    max_iters: int = 200,
    seed: int = 0,
    method: str = "gramfree",   # legacy default (svd() uses "block")
    op: HostBlockedMatrix | None = None,
    warmup_q: int = 0,
    oversample: int = 8,
    sweep_dtype: str = "float32",
) -> SVDResult:
    """Deprecated: use ``repro.core.svd(A_host, k, ...)`` — a numpy array
    (or a pre-built ``HostBlockedMatrix``) dispatches to the out-of-core
    backend.

    Translates the legacy keyword spellings into an ``SVDConfig`` (this
    entrypoint's old default was ``method="gramfree"``) and delegates to
    the front door; an injected ``op`` is passed through as the input.
    """
    from repro.core.svd import svd, warn_legacy
    warn_legacy("oom_tsvd")
    cfg = SVDConfig(method=method, eps=eps, max_iters=max_iters,
                    warmup_q=warmup_q, oversample=oversample,
                    sweep_dtype=sweep_dtype, n_blocks=n_blocks, seed=seed)
    return svd(op if op is not None else np.asarray(A_host), k, config=cfg)
