#!/usr/bin/env python3
"""Chip smoke: drive ``svd()`` and ``SVDService`` once on a TPU, at real sizes.

    python3 chip_smoke.py            # one chip: dense, pallas, hostblocked, serving
    python3 chip_smoke.py --mesh4    # four chips: the row-sharded solve only

Every matrix is generated from ``--seed`` with a planted spectrum
``A = U_p diag(s) V_p^T + N``: ``U_p``/``V_p`` have orthonormal columns
(QR of a Gaussian, on the device), the rank is ``r = 2k``, and
``s[k] / s[k-1] = 0.3``, so each solve converges in a handful of passes.
The float64 reference for the leading ``k`` singular values is ``s``
itself; the optional Gaussian noise ``N`` has a spectral norm below a
stated bound ``B`` (Davidson–Szarek, failure probability ``e^-18``), so
by Weyl every ``sigma_i`` lies within ``tol * s_i + B`` of ``s_i``.

Phases (one chip, in this order):

* ``hostblocked`` — a 163840 x 32768 fp32 host ``np.ndarray`` (20 GiB,
  more than the chip's HBM) streamed H2D in 32 row blocks;
* ``serving``     — an ``SVDService`` burst of 16 same-shape jobs that the
  micro-batcher stacks, plus one host-blocked job, each checked against
  ``np.linalg.svd`` in float64;
* ``dense``       — a 65536 x 32768 fp32 ``jax.Array`` (8 GiB) resident in
  HBM; ``svd(A, 32)`` with fp32 and with bf16 sweeps;
* ``pallas``      — ``kernels.ops.block_gram_chain`` compiled for the chip
  on the same ``A``, fp32 and bf16, against ``kernels/ref.py``.

``--mesh4`` runs only the sharded phase: 262144 x 32768 fp32 (32 GiB),
generated row-sharded over four chips, solved by ``svd(A, 32, mesh=)``.

Each phase prints one JSON line; these are smoke numbers, not benchmark
results.  Demotion down the memory ladder is off in every phase and the
backend is asserted.  A failed check raises, and the script exits
non-zero without printing its last line, which is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
There is no CPU mode: ``main()`` refuses to run without a TPU.  The
phase functions take their sizes, and the tests run them tiny on the CPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compat import make_mesh, pvary  # noqa: E402
from repro.core import svd  # noqa: E402
from repro.core.oom import HostBlockedMatrix  # noqa: E402
from repro.core.precision import fp32_dots  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serving import JobStatus, SVDService  # noqa: E402

#: sigma tolerance (relative) per sweep dtype
SIGMA_TOL = {"float32": 1e-4, "bfloat16": 1e-2}
#: sweep eps per dtype: bf16 cannot resolve subspace angles below its
#: rounding floor, so its gap test stops at the looser README setting
SWEEP_EPS = {"float32": 1e-6, "bfloat16": 1e-4}
#: normwise tolerance of the Pallas chain against the jnp reference
KERNEL_TOL = {"float32": 1e-3, "bfloat16": 1e-2}
#: a generated row block stays under this many bytes on the device
GEN_BLOCK_BYTES = 512 << 20
#: Davidson–Szarek: P(||G||_2 > sqrt(m) + sqrt(n) + t) <= exp(-t^2 / 2)
_DS_T = 6.0


class SmokeError(RuntimeError):
    """A smoke check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# ---------------------------------------------------------------------------
# Planted-spectrum data, generated on the device
# ---------------------------------------------------------------------------

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


def _row_blocks(m: int, n: int) -> int:
    """Fewest row blocks that divide ``m`` and keep a block under
    ``GEN_BLOCK_BYTES``."""
    nb = max(1, -(-m * n * 4 // GEN_BLOCK_BYTES))
    while m % nb:
        nb += 1
    return nb


class Planted:
    """The factors of one planted matrix, on the device.

    ``U`` is ``(m, r)`` with orthonormal columns, ``W = (V diag(s))^T``
    is ``(r, n)``; row block ``b`` of ``A`` is ``U[rows] @ W`` plus
    noise drawn from ``fold_in(key, b)``.
    """

    def __init__(self, m: int, n: int, k: int, seed: int,
                 noise_rel: float = 0.0):
        self.m, self.n, self.k = m, n, k
        self.s = planted_spectrum(k)
        r = self.s.size
        check(r <= min(m, n), f"rank {r} exceeds {(m, n)}")
        self.bound = noise_rel * float(self.s[k - 1])   # B >= ||N||_2
        self.tau = noise_tau(m, n, self.bound)
        ku, kv, self.key = jax.random.split(jax.random.key(seed), 3)
        with fp32_dots():
            self.U = _orthonormal(ku, m, r)
            V = _orthonormal(kv, n, r)
            self.W = (V * jnp.asarray(self.s, jnp.float32)).T

    def check_sigma(self, S, tol: float, what: str) -> float:
        """Max relative sigma error; raises unless every sigma lies
        within ``tol * s_i + B`` of the planted ``s_i``."""
        k = self.k
        S = np.asarray(S, np.float64)[:k]
        ref = self.s[:k]
        err = np.abs(S - ref)
        check(np.all(np.isfinite(S)), f"{what}: non-finite sigma {S}")
        check(np.all(err <= tol * ref + self.bound),
              f"{what}: sigma off the planted spectrum by "
              f"{float(np.max(err / ref)):.3e} relative (tol {tol}, "
              f"noise bound {self.bound:.3e})")
        return float(np.max(err / ref))

    def dense(self) -> jax.Array:
        """The whole ``(m, n)`` matrix on the default device, filled in
        place block by block (peak: ``A`` plus one block)."""
        nb = _row_blocks(self.m, self.n)
        with fp32_dots():
            return _fill(self.U, self.W, self.key, self.tau,
                         rows=self.m // nb)

    def sharded(self, mesh) -> jax.Array:
        """The matrix row-sharded over ``mesh``'s ``data`` axis, each
        slab generated on its own device (never whole anywhere)."""
        d = mesh.shape["data"]
        check(self.m % d == 0, f"m={self.m} not divisible by {d} devices")
        m_loc = self.m // d
        rows = m_loc // _row_blocks(m_loc, self.n)
        U = jax.device_put(self.U, NamedSharding(mesh, P("data", None)))
        with fp32_dots():
            return sharded_fill_fn(mesh, rows)(U, self.W, self.key,
                                               jnp.float32(self.tau))

    def host(self, n_blocks: int) -> np.ndarray:
        """The matrix as a host ``np.ndarray``: each row block is made
        on the device and copied into one preallocated host array (the
        next block is dispatched before the current one is copied)."""
        check(self.m % n_blocks == 0,
              f"m={self.m} not divisible by {n_blocks} blocks")
        rows = self.m // n_blocks
        out = np.empty((self.m, self.n), np.float32)
        with fp32_dots():
            nxt = _block(self.U[:rows], self.W,
                         jax.random.fold_in(self.key, 0), self.tau)
            for b in range(n_blocks):
                cur = nxt
                if b + 1 < n_blocks:
                    lo = (b + 1) * rows
                    nxt = _block(self.U[lo:lo + rows], self.W,
                                 jax.random.fold_in(self.key, b + 1),
                                 self.tau)
                out[b * rows:(b + 1) * rows] = np.asarray(cur)
        return out


@functools.partial(jax.jit, static_argnames=("m", "r"))
def _orthonormal(key, m: int, r: int):
    return jnp.linalg.qr(jax.random.normal(key, (m, r), jnp.float32))[0]


@jax.jit
def _block(U_rows, W, key, tau):
    noise = jax.random.normal(key, (U_rows.shape[0], W.shape[1]),
                              jnp.float32)
    return U_rows @ W + tau * noise


def _fill_body(U, W, key, tau, rows: int, vary=None):
    m, n = U.shape[0], W.shape[1]
    A0 = jnp.zeros((m, n), jnp.float32)

    def body(b, A):
        lo = b * rows
        blk = _block(jax.lax.dynamic_slice_in_dim(U, lo, rows), W,
                     jax.random.fold_in(key, b), tau)
        return jax.lax.dynamic_update_slice_in_dim(A, blk, lo, axis=0)

    return jax.lax.fori_loop(0, m // rows, body,
                             pvary(A0, vary) if vary else A0)


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


# ---------------------------------------------------------------------------
# Shared measurement helpers
# ---------------------------------------------------------------------------

def peak_bytes(device=None):
    """``peak_bytes_in_use`` of ``device`` (the default device), or None
    where the backend keeps no memory statistics."""
    stats = (device or jax.devices()[0]).memory_stats()
    return None if not stats else int(stats["peak_bytes_in_use"])


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _solve_twice(A, k: int, **kw):
    """``svd`` twice: the first call compiles, the second is warm."""
    res1, t1 = _timed(lambda: svd(A, k, demote_on_oom=False, **kw))
    res2, t2 = _timed(lambda: svd(A, k, demote_on_oom=False, **kw))
    return res1, res2, t1, t2


def _check_solve(res, backend: str, what: str) -> None:
    check(res.backend == backend,
          f"{what}: ran on backend {res.backend!r}, wanted {backend!r}")
    check(bool(res.converged), f"{what}: did not converge "
          f"(iters {int(np.max(res.iters))}, passes {res.passes_over_A})")
    check(not (res.faults or {}).get("counters"),
          f"{what}: fault telemetry recorded {res.faults}")


def _record(phase: str, res, shape, dtype: str, k: int, t1: float,
            t2: float, err: float, tol: float, **extra) -> dict:
    rec = {"phase": phase, "smoke": True, "shape": list(shape),
           "dtype": dtype, "k": k, "backend": res.backend,
           "converged": bool(res.converged),
           "iters": int(np.max(res.iters)),
           "passes_over_A": int(res.passes_over_A),
           "bytes_moved": res.bytes_moved,
           "wall_s_first": t1, "wall_s_second": t2,
           "peak_bytes_in_use": peak_bytes(),
           "sigma_max_rel_err": err, "sigma_tol": tol}
    rec.update(extra)
    return rec


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_dense(A: jax.Array, planted: Planted, k: int) -> list[dict]:
    """``svd`` of a device-resident ``A`` with fp32 and bf16 sweeps."""
    recs = []
    for sd in ("float32", "bfloat16"):
        what = f"dense/{sd}"
        res, res2, t1, t2 = _solve_twice(A, k, sweep_dtype=sd,
                                         eps=SWEEP_EPS[sd])
        for r in (res, res2):
            _check_solve(r, "dense", what)
        err = planted.check_sigma(res2.S, SIGMA_TOL[sd], what)
        recs.append(_record("dense", res2, A.shape, sd, k, t1, t2, err,
                            SIGMA_TOL[sd]))
    return recs


def phase_pallas(A: jax.Array, k: int, seed: int) -> list[dict]:
    """The Pallas ``block_gram_chain`` compiled (never interpreted) on
    the chip, against the jnp reference at full fp32 precision."""
    recs = []
    Q = _orthonormal(jax.random.key(seed + 1), A.shape[1], k)
    for sd in ("float32", "bfloat16"):
        dt = None if sd == "float32" else sd
        fn = jax.jit(functools.partial(ops.block_gram_chain,
                                       interpret=False, dtype=dt))
        t0 = time.perf_counter()
        compiled = fn.lower(A, Q).compile()
        t_compile = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"pallas/{sd}: no tpu_custom_call in the compiled program")
        Z, t1 = _timed(lambda: compiled(A, Q))
        _, t2 = _timed(lambda: compiled(A, Q))
        with fp32_dots():
            Zr = jax.jit(ops.block_gram_chain_ref,
                         static_argnums=2)(A, Q, dt)
        rel = float(jnp.linalg.norm(Z - Zr) / jnp.linalg.norm(Zr))
        check(np.isfinite(rel) and rel <= KERNEL_TOL[sd],
              f"pallas/{sd}: chain off the reference by {rel:.3e} "
              f"(tol {KERNEL_TOL[sd]})")
        recs.append({"phase": "pallas", "smoke": True,
                     "shape": list(A.shape), "dtype": sd, "k": k,
                     "backend": "pallas", "compile_s": t_compile,
                     "wall_s_first": t1, "wall_s_second": t2,
                     "peak_bytes_in_use": peak_bytes(),
                     "rel_err_vs_ref": rel, "tol": KERNEL_TOL[sd]})
    return recs


def host_ram_bytes() -> tuple[int, int]:
    """(free, total) physical host memory."""
    page = os.sysconf("SC_PAGE_SIZE")
    return (page * os.sysconf("SC_AVPHYS_PAGES"),
            page * os.sysconf("SC_PHYS_PAGES"))


def phase_hostblocked(m: int, n: int, k: int, n_blocks: int, seed: int,
                      noise_rel: float = 1e-6) -> dict:
    """``svd`` of a host ``np.ndarray`` streamed H2D in row blocks."""
    need = m * n * 4
    free, total = host_ram_bytes()
    headroom = 2 << 30
    check(need + headroom <= free,
          f"hostblocked: a {m}x{n} fp32 host matrix needs {need} B plus "
          f"{headroom} B headroom, the host has {free} B free of {total} "
          f"(short by {need + headroom - free} B); not shrinking")
    planted = Planted(m, n, k, seed, noise_rel)
    A, t_gen = _timed(lambda: planted.host(n_blocks))
    res, res2, t1, t2 = _solve_twice(A, k, n_blocks=n_blocks)
    for r in (res, res2):
        _check_solve(r, "hostblocked", "hostblocked")
    err = planted.check_sigma(res2.S, SIGMA_TOL["float32"], "hostblocked")
    h2d = res2.bytes_moved["host"]
    return _record("hostblocked", res2, (m, n), "float32", k, t1, t2, err,
                   SIGMA_TOL["float32"], n_blocks=n_blocks,
                   gen_s=t_gen, h2d_GBps=h2d / t2 / 1e9)


def _sigma_ref(A: np.ndarray, k: int) -> np.ndarray:
    return np.linalg.svd(np.asarray(A, np.float64), compute_uv=False)[:k]


def _serve_once(burst, big, k: int, n_blocks: int):
    """One service lifetime: the burst (stacked by the micro-batcher)
    and one host-blocked job; returns handles, records, wall seconds."""
    t0 = time.perf_counter()
    with SVDService(max_workers=2, batch_window_s=1.0,
                    max_batch=len(burst)) as svc:
        hs = [svc.submit(A, k, tag=f"burst-{i}", seed=i)
              for i, A in enumerate(burst)]
        hb = svc.submit(HostBlockedMatrix(big, n_blocks), k, tag="big")
        results = [h.result(600.0) for h in hs + [hb]]
        for h in hs + [hb]:
            check(h.status is JobStatus.DONE, f"serving: {h.job_id} "
                  f"ended {h.status.value} ({h.error})")
        recs = {r.tag: r for r in svc.meter.records}
    return results, recs, time.perf_counter() - t0


def phase_serving(m: int, n: int, k: int, n_jobs: int, big_shape,
                  seed: int, n_blocks: int = 4) -> dict:
    """An ``SVDService`` burst of same-shape device jobs plus one
    host-blocked job, every result against float64 LAPACK."""
    check(n_jobs > 1, "serving: a burst needs more than one job")
    burst = [Planted(m, n, k, seed + i).dense() for i in range(n_jobs)]
    big = Planted(*big_shape, k, seed + n_jobs).host(n_blocks)
    refs = [_sigma_ref(A, k) for A in burst] + [_sigma_ref(big, k)]
    tol = SIGMA_TOL["float32"]
    walls, errs = [], []
    for _ in range(2):                 # first lifetime compiles
        results, recs, wall = _serve_once(burst, big, k, n_blocks)
        walls.append(wall)
        for i, (res, ref) in enumerate(zip(results, refs)):
            what = f"serving/job {i}"
            check(bool(res.converged), f"{what}: did not converge")
            err = np.abs(np.asarray(res.S, np.float64)[:k] - ref) / ref
            check(np.all(err <= tol), f"{what}: sigma off LAPACK by "
                  f"{float(np.max(err)):.3e} (tol {tol})")
            errs.append(float(np.max(err)))
        for i in range(n_jobs):
            r = recs[f"burst-{i}"]
            check(r.batched and r.batch_size > 1 and r.backend == "dense",
                  f"serving: burst job {i} was not micro-batched "
                  f"(batched={r.batched}, batch_size={r.batch_size}, "
                  f"backend={r.backend})")
        check(recs["big"].backend == "hostblocked" and not
              recs["big"].batched, f"serving: the big job ran as "
              f"{recs['big'].backend}, batched={recs['big'].batched}")
    return {"phase": "serving", "smoke": True, "shape": [m, n],
            "dtype": "float32", "k": k, "jobs": n_jobs + 1,
            "batch_sizes": sorted({recs[f"burst-{i}"].batch_size
                                   for i in range(n_jobs)}),
            "big_shape": list(big_shape), "big_backend": "hostblocked",
            "converged": True,
            "iters": int(max(int(np.max(r.iters)) for r in results)),
            "passes_over_A": int(sum(r.passes_over_A for r in results)),
            "wall_s_first": walls[0], "wall_s_second": walls[1],
            "peak_bytes_in_use": peak_bytes(),
            "sigma_max_rel_err": max(errs), "sigma_tol": tol}


def phase_sharded(devices, m: int, n: int, k: int, seed: int,
                  noise_rel: float = 1e-6) -> dict:
    """``svd(A, k, mesh=)`` of a matrix generated row-sharded over
    ``devices``: each device holds only its own slab."""
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    planted = Planted(m, n, k, seed, noise_rel)
    A, t_gen = _timed(lambda: planted.sharded(mesh))
    res, res2, t1, t2 = _solve_twice(A, k, mesh=mesh)
    for r in (res, res2):
        _check_solve(r, "sharded", "sharded")
    err = planted.check_sigma(res2.S, SIGMA_TOL["float32"], "sharded")
    slab, whole = m * n * 4 // len(devices), m * n * 4
    peaks = [peak_bytes(d) for d in devices]
    if all(p is not None for p in peaks):
        for d, p in zip(devices, peaks):
            check(slab <= p < min(whole, 2 * slab),
                  f"sharded: device {d.id} peaked at {p} B; its slab is "
                  f"{slab} B of {whole} B")
    return _record("sharded", res2, (m, n), "float32", k, t1, t2, err,
                   SIGMA_TOL["float32"], devices=len(devices),
                   gen_s=t_gen, slab_bytes=slab,
                   peak_bytes_per_device=peaks)


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh4", action="store_true",
                    help="run only the 4-chip row-sharded phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); there is no CPU mode",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"jax {jax.__version__}  device_kind {dev.device_kind}  "
          f"devices {len(devices)}  "
          f"bytes_limit {dev.memory_stats()['bytes_limit']}  "
          f"compile_cache {cache}", flush=True)

    if args.mesh4:
        check(len(devices) >= 4, f"--mesh4 needs 4 chips, JAX found "
              f"{len(devices)}")
        used = devices[:4]
        emit(phase_sharded(used, 262144, 32768, 32, args.seed))
    else:
        # the streamed phases come first: peak_bytes_in_use only grows,
        # so each phase's peak is its own only while it is the largest
        used = devices[:1]
        k = 32
        emit(phase_hostblocked(163840, 32768, k, 32, args.seed + 1))
        emit(phase_serving(4096, 1024, 16, 16, (8192, 2048),
                           args.seed + 2))
        planted = Planted(65536, 32768, k, args.seed, noise_rel=1e-6)
        A = planted.dense()
        for rec in phase_dense(A, planted, k):
            emit(rec)
        for rec in phase_pallas(A, k, args.seed):
            emit(rec)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(used)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
