"""Micro-batcher: a burst of small same-shape solves as ONE dispatch.

A serving process sees storms of small decompositions (per-user
embedding blocks, per-layer weight tiles) where the python driver loop
plus per-iteration dispatch costs more than the math.  The batcher
groups queued jobs by ``batch_key`` — identical (m, n, k, solver
fingerprint, dtype) — stacks their inputs into an ``(B, m, n)`` block,
and runs the SAME block subspace iteration the engine runs per job
(``sweep_ops`` gram chain, thin-QR orthonormalization, rotation-
invariant subspace gap, Rayleigh–Ritz extraction — all from
``core/``), vmapped over the batch inside one jitted
``lax.while_loop``.  One compile serves every future burst of that
shape.

Contracts (locked down in ``tests/test_serving_batch.py``):

* **differential** — each lane's (S, subspace) agrees with a
  standalone per-job ``svd()`` at the same config, on both the dense
  and the host-blocked per-job baselines;
* **isolation** — vmap lanes are numerically independent, so a
  poisoned lane (NaN input, injected corruption) fails ALONE: its gap
  goes non-finite, the loop stops iterating it, and the per-lane
  health check fails just that job with the engine's typed
  ``NumericalHealthError`` while its batchmates complete;
* **honest accounting** — per-lane ``passes_over_A``/``bytes_moved``
  follow the engine's counting convention (2 passes per iteration +
  warmup + extraction) against the lane's own iteration count.

Stragglers — a flush with a single job, or any job whose input/config
the batcher cannot stack — fall back to the sequential runner
unchanged.
"""
from __future__ import annotations

import functools
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import SVDResult, seed_to_key
from repro.core.errors import NumericalHealthError
from repro.core.operator import warm_start_width
from repro.core.precision import fp32_dots, resolve_sweep_dtype
from repro.core.tsvd import rayleigh_ritz_from_W, sweep_ops

__all__ = ["batch_key", "batchable", "solve_batch",
           "batched_block_solve_fn", "MAX_BATCH_ELEMS"]

#: lanes bigger than this (16 MiB of fp32) are not worth stacking: the
#: solve dominates the dispatch overhead, and they inflate the batch's
#: memory peak
MAX_BATCH_ELEMS = 1 << 22


def batchable(spec) -> bool:
    """True iff this job can ride a vmapped batch: a small in-memory
    dense 2-D array, block method, no per-job plumbing (checkpoints,
    trace hooks, streaming) that needs the scalar driver."""
    cfg = spec.resolved_config()
    if cfg.method != "block" or cfg.on_iteration is not None:
        return False
    if cfg.checkpoint_dir is not None or cfg.force_iters:
        return False
    if getattr(spec, "stream_every", 0):
        return False
    A = spec.input
    if isinstance(A, np.memmap):         # staged tiers: never stack
        return False
    if not isinstance(A, (np.ndarray, jax.Array)):
        return False
    if A.ndim != 2 or A.shape[0] * A.shape[1] > MAX_BATCH_ELEMS:
        return False
    return min(A.shape) >= 1 and spec.k <= min(A.shape)


def batch_key(spec) -> tuple:
    """Jobs stack iff this key matches: same shape/rank and the same
    trajectory-defining solver knobs (``solver_fingerprint`` covers
    method, warmup, oversample, sweep dtype, seed-independent knobs)
    plus the budget knobs the loop bakes in statically."""
    cfg = spec.resolved_config()
    A = spec.input
    return (int(A.shape[0]), int(A.shape[1]), int(spec.k),
            cfg.method, cfg.warmup_q, cfg.oversample, cfg.sweep_dtype,
            float(cfg.eps), int(cfg.max_iters))


#: serializes builder-cache misses: ``lru_cache`` alone does NOT dedupe
#: concurrent first calls — racing worker threads would each build (and
#: later compile) their own copy of the same signature
_BUILDER_LOCK = threading.Lock()


@functools.lru_cache(maxsize=None)
def _batched_block_solve_fn(m: int, n: int, k: int, l: int,
                            sweep_dtype: str, eps: float,
                            max_iters: int, warmup_q: int):
    """Build (once per signature) the jitted batched block solve.

    Returns ``solve(X, keys) -> (U, S, V, iters, gaps, converged)`` with
    ``X: (B, m, n)`` stacked tall inputs and ``keys: (B,)`` per-lane PRNG
    keys; every output is per-lane.  B stays a traced batch dimension of
    the vmap, but jit still specializes on it via the argument shape —
    the cache that matters is the (shape, config) signature here, so a
    recurring burst shape compiles exactly once per B.

    The iteration mirrors ``core/svd.py::step`` in its unlagged form:
    ``Q <- orth(A^T A Q)``, gap ``||Qn - Q Q^T Qn||_F^2``, stop per lane at
    ``gap <= eps * l``.  Non-finite gaps also stop the lane (so a NaN
    lane cannot spin its batchmates to max_iters); the caller maps those
    lanes to typed failures.
    """
    tol = float(eps) * l

    def lane_chain(X, Q):
        mm, rmm = sweep_ops(X, sweep_dtype)
        return rmm(mm(Q))

    def lane_sketch(X, key):
        _, rmm = sweep_ops(X, sweep_dtype)
        Om = jax.random.normal(jax.random.fold_in(key, 1), (m, l),
                               jnp.float32)
        return rmm(Om)

    def lane_cold(key):
        return jax.random.normal(key, (n, l), jnp.float32)

    chain = jax.vmap(lane_chain)
    orth = jax.vmap(lambda X: jnp.linalg.qr(X)[0])
    extract = jax.vmap(lambda X, Q: rayleigh_ritz_from_W(X @ Q, Q))

    def gaps(Q, Qn):
        # per-lane rotation-invariant subspace gap (cf. operator._gap)
        R = Qn - Q @ jnp.einsum("bij,bik->bjk", Q, Qn)
        return jnp.sum(R ** 2, axis=(1, 2))

    def solve(X, keys):
        if warmup_q > 0:
            Q = orth(jax.vmap(lane_sketch)(X, keys))
            for _ in range(warmup_q):
                Q = orth(chain(X, Q))
        else:
            Q = orth(jax.vmap(lane_cold)(keys))
        B = Q.shape[0]
        state0 = (Q, jnp.zeros((B,), jnp.int32),
                  jnp.full((B,), jnp.inf, jnp.float32),
                  jnp.zeros((B,), bool))

        def cond(state):
            _, it, _, done = state
            return (~jnp.all(done)) & (it.max() < max_iters)

        def body(state):
            Q, it, gap, done = state
            Qn = orth(chain(X, Q))
            g = gaps(Q, Qn)
            # frozen lanes keep their converged iterate + final gap
            keep = done[:, None, None]
            Qn = jnp.where(keep, Q, Qn)
            g = jnp.where(done, gap, g)
            it = jnp.where(done, it, it + 1)
            done = done | (g <= tol) | ~jnp.isfinite(g)
            return (Qn, it, g, done)

        Q, iters, gap, done = jax.lax.while_loop(cond, body, state0)
        U, S, V = extract(X, Q)
        conv = done & (gap <= tol) & jnp.isfinite(gap)
        return (U[:, :, :k], S[:, :k], V[:, :, :k], iters, gap, conv)

    return jax.jit(solve)


def batched_block_solve_fn(m: int, n: int, k: int, l: int,
                           sweep_dtype: str, eps: float,
                           max_iters: int, warmup_q: int):
    """Race-free front of the cached builder: every thread asking for
    one signature gets the SAME jitted callable (one compile)."""
    with _BUILDER_LOCK:
        return _batched_block_solve_fn(m, n, k, l, sweep_dtype, eps,
                                       max_iters, warmup_q)


batched_block_solve_fn.cache_clear = _batched_block_solve_fn.cache_clear


def solve_batch(specs: list) -> list[tuple[Any, BaseException | None]]:
    """Run a stackable batch; returns one ``(SVDResult | None, error |
    None)`` per spec, positionally.  Lanes whose extraction came back
    non-finite get ``(None, NumericalHealthError)`` — the batch itself
    never raises for a poisoned lane.
    """
    cfg0 = specs[0].resolved_config()
    sd = resolve_sweep_dtype(cfg0.sweep_dtype).name
    A0 = specs[0].input
    m, n = int(A0.shape[0]), int(A0.shape[1])
    k = int(specs[0].k)
    tall = m >= n
    if not tall:
        m, n = n, m
    l = warm_start_width(k, cfg0.oversample, n) if cfg0.warmup_q > 0 else k

    X = jnp.stack([
        jnp.asarray(s.input if tall else np.asarray(s.input).T,
                    jnp.float32)
        for s in specs])
    keys = jnp.stack([seed_to_key(s.resolved_config().seed)
                      for s in specs])
    fn = batched_block_solve_fn(m, n, k, l, sd, float(cfg0.eps),
                                int(cfg0.max_iters), int(cfg0.warmup_q))
    with fp32_dots():
        U, S, V, iters, gap, conv = fn(X, keys)
    U, S, V = np.asarray(U), np.asarray(S), np.asarray(V)
    iters = np.asarray(iters)
    conv = np.asarray(conv)
    bpp = m * n * jnp.dtype(sd).itemsize

    out = []
    for i, s in enumerate(specs):
        if not np.all(np.isfinite(S[i])):
            err = NumericalHealthError(
                f"batched lane {i} produced non-finite singular values "
                f"(subspace gap {float(gap[i])}): the input contains "
                f"NaN/Inf or overflowed the {sd} sweep — the job fails "
                f"alone; its batchmates are unaffected", kind="nonfinite")
            out.append((None, err))
            continue
        it = int(iters[i])
        cfg = s.resolved_config()
        # engine accounting convention: sketch pass + 2-pass warmup
        # chains, 2 passes per iteration, 1 extraction pass
        passes = (cfg.warmup_q * 2 + 1 if cfg.warmup_q > 0 else 0) \
            + 2 * it + 1
        Ui, Vi = (U[i], V[i]) if tall else (V[i], U[i])
        res = SVDResult(
            Ui, S[i], Vi, np.full((k,), it, np.int32), passes, bpp,
            bool(conv[i]), "dense",
            bytes_moved={"device": passes * bpp})
        out.append((res, None))
    return out
