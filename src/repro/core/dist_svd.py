"""Distributed deflation t-SVD engine over a named mesh axis (Algs 3 & 4).

The paper's N-GPU layout maps 1:1 onto a JAX mesh axis:

* ``A`` row-sharded over the axis (RSVD; wide inputs are transposed in and
  the factors swapped out, recovering CSVD),
* ``U`` row-sharded alongside ``A``,
* ``Sigma`` and ``V`` replicated,
* NCCL all-reduce  ->  ``jax.lax.psum`` / ``psum_scatter``,
* per-GPU batched tiles -> an in-shard ``lax.scan`` over row blocks
  (XLA double-buffers the blocks, playing the CUDA-stream role).

This module holds the rank-one **deflation** engine in two fidelity
levels, benchmarked separately (§Perf):

* ``faithful=True``  — the paper's collective schedule: Alg 4 issues its
  three separate all-reduces (lines 6, 8, 16); the Alg-3 Gram is replicated
  on every worker before power iteration.
* ``faithful=False`` (default) — beyond-paper optimizations:
  (1) the two n-vector all-reduces of Alg 4 fuse into one by linearity
      (``X^T(Xv) - X^T U S V^T v = X^T (Xv - U(S V^T v))``),
  (2) the k-vector reduce rides in the same payload (single collective per
      power step),
  (3) the Gram path keeps ``B`` *row-sharded* (reduce-scatter instead of
      all-reduce) so per-chip memory and mat-vec FLOPs drop by N, at the
      cost of one all-gather of the iterate per step.

The **block** method on this backend — one fused ``(n, k)`` psum per
step advancing all k ranks, per-shard warm-start sketches, Rayleigh–Ritz
through the psum'd ``(k, k)`` Gram — lives in
``core/operator.py::ShardedOperator`` and runs through the shared driver
(``repro.core.svd``); there is no copy of it here.  ``dist_tsvd()`` is
the deprecated back-compat shim onto the front door.

Pass accounting matches ``core/tsvd.py``: the faithful chain costs 3
A-sweeps per power step, the fused chain 2, plus one u-recovery sweep
per rank; the Gram path 3 per rank.  Counts are dtype-independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# varying -> invariant all-gather (replicated output) + version shims
from repro.compat import all_gather_inv as _all_gather_inv
from repro.compat import pvary as _pvary
from repro.core.config import SVDConfig, SVDResult

#: Back-compat alias — the per-backend result NamedTuples were unified.
DistTSVDResult = SVDResult


def _norm(x):
    return jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))


def _psum_norm(x, axes):
    return jnp.sqrt(jax.lax.psum(jnp.sum(x.astype(jnp.float32) ** 2), axes))


# ---------------------------------------------------------------------------
# Local (per-shard) kernels used inside shard_map
# ---------------------------------------------------------------------------

def _deflated_chain_step(A_loc, U_loc, S, V, v, axes, *, faithful, n_blocks):
    """One Alg-4 power step on the row-sharded residual operator.

    Returns the *unnormalized* ``v1`` (replicated).  ``A_loc: (m_loc, n)``,
    ``U_loc: (m_loc, k)``, ``S: (k,)``, ``V: (n, k)``, ``v: (n,)``.
    """
    k = S.shape[0]
    Vtv = V.T @ v                       # (k,) replicated
    SVtv = S * Vtv

    if faithful:
        # Paper's schedule: three all-reduces (Alg 4 lines 6, 8, 16).
        Xv = A_loc @ v                                   # (m_loc,) local
        t1 = jax.lax.psum(A_loc.T @ Xv, axes)            # line 6
        UtXv = jax.lax.psum(U_loc.T @ Xv, axes)          # line 8
        t2 = V @ (S * UtXv)
        t3 = jax.lax.psum(A_loc.T @ (U_loc @ SVtv), axes)  # line 16
        t4 = V @ (S * S * Vtv)
        return t1 - t2 - t3 + t4

    # Optimized: fused sweep + single concatenated all-reduce.
    if n_blocks <= 1:
        Xv = A_loc @ v
        t13_part = A_loc.T @ (Xv - U_loc @ SVtv)         # (n,)
        utxv_part = U_loc.T @ Xv                         # (k,)
    else:
        # In-shard OOM batching: scan over row blocks (paper's n_b batches);
        # XLA pipelines block loads against MXU work (the q_s>1 effect).
        m_loc = A_loc.shape[0]
        rows_b = m_loc // n_blocks
        A_blk = A_loc[: rows_b * n_blocks].reshape(n_blocks, rows_b, -1)
        U_blk = U_loc[: rows_b * n_blocks].reshape(n_blocks, rows_b, k)

        def step(carry, xs):
            acc_n, acc_k = carry
            a_b, u_b = xs
            xv_b = a_b @ v
            acc_n = acc_n + a_b.T @ (xv_b - u_b @ SVtv)
            acc_k = acc_k + u_b.T @ xv_b
            return (acc_n, acc_k), None

        n = A_loc.shape[1]
        init = (jnp.zeros((n,), jnp.float32), jnp.zeros((k,), jnp.float32))
        init = _pvary(init, tuple(axes))  # carries vary per shard
        (t13_part, utxv_part), _ = jax.lax.scan(step, init, (A_blk, U_blk))
        if rows_b * n_blocks != m_loc:  # ragged tail
            a_t = A_loc[rows_b * n_blocks:]
            u_t = U_loc[rows_b * n_blocks:]
            xv_t = a_t @ v
            t13_part = t13_part + a_t.T @ (xv_t - u_t @ SVtv)
            utxv_part = utxv_part + u_t.T @ xv_t

    fused = jnp.concatenate([t13_part, utxv_part])       # (n + k,)
    fused = jax.lax.psum(fused, axes)                    # ONE collective
    t13, UtXv = fused[: v.shape[0]], fused[v.shape[0]:]
    return t13 - V @ (S * UtXv) + V @ (S * S * Vtv)


def _power_loop(matvec, v0, *, eps, max_iters, force_iters, axes=None):
    """Replicated-consistent power iteration (all shards agree on `done`).

    ``axes`` marks the carry as mesh-varying when run inside shard_map
    (values are bitwise-identical across shards — psum outputs — but the
    vma type system tracks them as varying).
    """

    def cond(state):
        i, _, done = state
        if force_iters:
            return i < max_iters
        return jnp.logical_and(i < max_iters, jnp.logical_not(done))

    def body(state):
        i, v, _ = state
        v1 = matvec(v)
        v1 = v1 / (_norm(v1) + 1e-30)
        done = jnp.abs(jnp.vdot(v, v1)) >= 1.0 - eps
        return i + 1, v1, done

    v0 = v0 if axes is None else _pvary(v0, axes)
    done0 = jnp.array(False) if axes is None else _pvary(
        jnp.array(False), axes)
    init = (jnp.array(0, jnp.int32), v0, done0)
    iters, v, _ = jax.lax.while_loop(cond, body, init)
    return v, iters


# ---------------------------------------------------------------------------
# Deflation engine (called by the front door for gram/gramfree)
# ---------------------------------------------------------------------------

def _dist_deflation(
    A: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    axes: tuple[str, ...],
    method: str,            # "gram" | "gramfree"
    faithful: bool,
    n_blocks: int,
    eps: float,
    max_iters: int,
    force_iters: bool,
    seed: int,
):
    """Rank-one deflation on ``A`` row-sharded over ``axes`` of ``mesh``.

    Expects the tall orientation (the front door transposes wide inputs
    and swaps the factors); ``m`` must be divisible by the product of
    the mesh axis sizes.  Returns ``(U, S, V, iters, passes)`` with
    ``U`` row-sharded and everything else replicated.
    """
    m, n = A.shape
    nshards = 1
    for a in axes:
        nshards *= mesh.shape[a]
    if m % nshards:
        raise ValueError(f"m={m} not divisible by shards={nshards}; pad first")

    row_spec = P(axes if len(axes) > 1 else axes[0], None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(row_spec, P(None)),
        out_specs=(row_spec, P(None), P(None, None), P(None), P(None)),
    )
    def run(A_loc, seed_arr):
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed_arr[0])
        m_loc = A_loc.shape[0]
        A32 = A_loc.astype(jnp.float32)

        U_loc = _pvary(jnp.zeros((m_loc, k), jnp.float32), axes)
        S = jnp.zeros((k,), jnp.float32)
        V = jnp.zeros((n, k), jnp.float32)
        iters_out = jnp.zeros((k,), jnp.int32)
        keys = jax.random.split(key, k)

        def rank_step(l, carry):
            U_loc, S, V, iters_out = carry
            v0 = jax.random.normal(keys[l], (n,), jnp.float32)
            v0 = v0 / _norm(v0)

            if method == "gram":
                # Residual Gram once per rank (paper's dense path, Alg 3).
                X_loc = A32 - (U_loc * S[None, :]) @ V.T
                if faithful:
                    B = jax.lax.psum(X_loc.T @ X_loc, axes)   # replicated B
                    mv = lambda v: B @ v
                else:
                    # Row-sharded B: reduce-scatter + per-step all-gather.
                    B_loc = jax.lax.psum_scatter(
                        X_loc.T @ X_loc, axes[0], scatter_dimension=0,
                        tiled=True) if len(axes) == 1 else jax.lax.psum(
                        X_loc.T @ X_loc, axes)
                    if len(axes) == 1:
                        mv = lambda v: _all_gather_inv(
                            B_loc @ v, axes[0], tiled=True)
                    else:
                        mv = lambda v: B_loc @ v
                v, iters = _power_loop(
                    mv, v0, eps=eps, max_iters=max_iters,
                    force_iters=force_iters)
            else:
                mv = lambda v: _deflated_chain_step(
                    A32, U_loc, S, V, v, axes,
                    faithful=faithful, n_blocks=n_blocks)
                v, iters = _power_loop(
                    mv, v0, eps=eps, max_iters=max_iters,
                    force_iters=force_iters)

            # u = (A - U S V^T) v  (deflated so duplicates stay orthogonal)
            u_loc = A32 @ v - U_loc @ (S * (V.T @ v))
            sigma = _psum_norm(u_loc, axes)
            u_loc = u_loc / (sigma + 1e-30)
            U_loc = U_loc.at[:, l].set(u_loc)
            S = S.at[l].set(sigma)
            V = V.at[:, l].set(v)
            iters_out = iters_out.at[l].set(iters)
            return U_loc, S, V, iters_out

        U_loc, S, V, iters_out = jax.lax.fori_loop(
            0, k, rank_step, (U_loc, S, V, iters_out))
        if method == "gram":
            # Gram path: residual + Gram + u recovery per rank; the power
            # loop itself runs on the small replicated/sharded B.
            passes = jnp.asarray(3 * k, jnp.int32)
        else:
            # chain: 3 A-sweeps/step faithful, 2 fused; + u recovery/rank.
            per_step = 3 if faithful else 2
            passes = (per_step * jnp.sum(iters_out) + k).astype(jnp.int32)
        return U_loc, S, V, iters_out, jnp.reshape(passes, (1,))

    A_sharded = jax.device_put(A, NamedSharding(mesh, row_spec))
    U, S, V, iters, passes = jax.jit(run)(
        A_sharded, jnp.array([seed & 0xFFFFFFFF], jnp.uint32))
    return U, S, V, iters, passes[0]


# ---------------------------------------------------------------------------
# Deprecated back-compat shim
# ---------------------------------------------------------------------------

def dist_tsvd(
    A: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    axes: tuple[str, ...] = ("data",),
    method: str = "gramfree",       # legacy default (svd() uses "block")
    faithful: bool = False,
    n_blocks: int = 1,
    eps: float = 1e-6,
    max_iters: int = 200,
    force_iters: bool = False,
    seed: int = 0,
    warmup_q: int = 0,
    oversample: int = 8,
    sweep_dtype: str = "float32",
) -> SVDResult:
    """Deprecated: use ``repro.core.svd(A, k, mesh=mesh, axes=axes, ...)``.

    Translates the legacy keyword spellings into an ``SVDConfig`` (this
    entrypoint's old default was ``method="gramfree"``) and delegates to
    the front door.
    """
    from repro.core.svd import svd, warn_legacy
    warn_legacy("dist_tsvd")
    if method == "block" and n_blocks > 1:  # legacy contract preserved
        raise ValueError("method='block' supports neither faithful=True "
                         "nor n_blocks > 1 (its step is one fused matmat)")
    cfg = SVDConfig(method=method, eps=eps, max_iters=max_iters,
                    force_iters=force_iters, warmup_q=warmup_q,
                    oversample=oversample, sweep_dtype=sweep_dtype,
                    n_blocks=max(n_blocks, 1), seed=seed,
                    faithful=faithful)
    return svd(A, k, mesh=mesh, axes=axes, config=cfg)


# ---------------------------------------------------------------------------
# Faithful Alg-4 mat-vec (exported for tests / §Perf baseline)
# ---------------------------------------------------------------------------

def deflated_matvec_faithful(A_loc, U_loc, S, V, v, axes):
    """Paper-faithful Alg-4 step (three collectives), for benchmarking."""
    return _deflated_chain_step(A_loc, U_loc, S, V, v, axes,
                                faithful=True, n_blocks=1)
