"""Unified configuration + result types for the single SVD front door.

Every knob the four execution regimes (in-memory, distributed,
out-of-core, sparse-streamed) used to spell differently lives here,
validated in ONE place:

* ``SVDConfig`` — a frozen dataclass holding every solver knob.  Adding
  the next knob is a one-file change: add the field + its validation
  here, read it in the shared driver (``core/svd.py``) or the operator
  adapter that needs it (``core/operator.py``).  Fields are hashable
  Python scalars so a config can be used as a jit-static value.
* ``SVDResult`` — the one result tuple all backends return.  The first
  five fields are exactly the legacy result-tuple fields (``U, S, V,
  iters, passes_over_A``), so code written against the old per-backend
  NamedTuples keeps working unchanged (including ``res[:3]`` slicing);
  the new fields add the byte accounting and dispatch metadata.

Legacy-spelling notes (what this module unifies — see the shims in
``tsvd``/``dist_svd``/``oom``/``sparse`` for the old surfaces):

* RNG: one integer ``seed`` everywhere.  The serial path used to take a
  jax PRNG ``key``; ``key_to_seed`` recovers the integer from a
  ``PRNGKey(s)`` so the shim translation is exact.
* ``force_iters`` now exists on every backend (the OOM and sparse
  entrypoints silently lacked it).
* one documented default ``method="block"`` — the recommended solver
  (``tsvd`` used to default to ``"gram"``, the other three to
  ``"gramfree"``; the deprecated shims pin their old defaults).
* blocking: ``n_blocks`` (host-block count, OOM staging / in-shard
  deflation batching) and ``block_rows`` (rows per generated block,
  sparse streaming) both live here instead of being per-entrypoint
  spellings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import numpy as np

from repro.core.errors import InputError
from repro.core.precision import SWEEP_DTYPES, resolve_sweep_dtype

METHODS = ("gram", "gramfree", "block")

#: backend tags reported in ``SVDResult.backend``
BACKENDS = ("dense", "sharded", "hostblocked", "memmap", "sparsestream",
            "scipysparse", "operator")


@dataclasses.dataclass(frozen=True)
class SVDConfig:
    """All solver knobs, validated once.

    ``method``       "gram" | "gramfree" (rank-one deflation, the paper's
                     Alg 1/2/4) or "block" (block subspace iteration —
                     the default and the recommended solver: every pass
                     over ``A`` advances all k ranks).
    ``eps``          convergence tolerance (subspace gap for "block",
                     ``|v . v1| >= 1 - eps`` for deflation).
    ``max_iters``    iteration cap (per rank for deflation).
    ``force_iters``  disable the convergence test (the paper's scaling-
                     benchmark mode) — run exactly ``max_iters``.
    ``warmup_q``     block only: randomized range-finder warm start
                     ``Q0 = orth((A^T A)^q A^T Omega)`` (0 = cold start).
    ``oversample``   block only: extra sketch columns p (iterate width
                     ``l = k + p``, truncated at extraction).
    ``sweep_dtype``  block only: "float32" | "bfloat16" operand dtype of
                     the A-sized sweeps (fp32 accumulation; see
                     ``core/precision.py``).
    ``n_blocks``     host-block count for the out-of-core backend (H2D
                     staging granularity) and in-shard deflation batching
                     on the sharded backend.  The default (4) is tuned
                     for OOM staging; pass ``n_blocks=1`` on the sharded
                     deflation path for the unbatched legacy step (the
                     legacy ``dist_tsvd`` shim pins 1, so its results
                     are unchanged; batching only reorders the in-shard
                     FP accumulation).  The block method has no batching
                     here — its step is one fused matmat.
    ``block_rows``   rows per generated block on the sparse-streamed
                     backend.
    ``host_budget_bytes``  disk tier (memmap) only: cap on the host-side
                     staged-block cache.  ``0`` (default) = unbounded —
                     blocks are cached after the first cold read; ``> 0``
                     bounds host RAM, re-reading evicted blocks from
                     disk (LRU).  The cap covers the cache, not the one
                     block in flight.
    ``seed``         the one RNG convention: an integer seed.
    ``faithful``     sharded deflation only: the paper's collective
                     schedule (three all-reduces per step) instead of the
                     fused single-collective step.
    ``checkpoint_dir``  block only: persist the ``SolverState`` through
                     ``checkpoint.CheckpointManager`` (atomic step dirs)
                     and AUTO-RESUME from ``latest_step()`` on the next
                     call when the config/operator fingerprints match
                     (a mismatch errors loudly).  ``None`` disables.
    ``checkpoint_every``  save every N block iterations (``1`` = every
                     iteration; a final state is always saved at loop
                     exit).  Each save host-syncs the convergence
                     scalar, trading a little pipeline lag for
                     durability.
    ``on_iteration``  block only: trace hook called with the new
                     ``SolverState`` after every iteration — the one
                     sanctioned way to observe per-iteration gap/pass/
                     byte trajectories (benchmarks and tests use it
                     instead of instrumenting operators ad hoc).  Note
                     ``state.gap`` may be an unsynced device scalar;
                     ``float()`` it only if you accept the sync.
    ``io_retries``   total attempts (1 = no retry) for each transient
                     staging operation — the memmap disk read and the
                     H2D block copy — under exponential backoff with
                     deterministic jitter (``core/faults.py::retry_io``).
                     Exhaustion raises ``FaultExhaustedError``; every
                     retry/giveup is reported in ``SVDResult.faults``.
    ``io_retry_backoff``  base backoff delay in seconds (doubles per
                     attempt, capped at 2s; 0 = retry immediately —
                     the chaos tests use 0 to stay fast).
    ``health_retries``  block only: bounded rollback/re-orth attempts of
                     the numeric health guard before the solve raises
                     ``FaultExhaustedError``.  The counter resets every
                     confirmed-healthy step, so it bounds *consecutive*
                     failures, not lifetime ones.
    ``demote_on_oom``  block only: on device RESOURCE_EXHAUSTED, demote
                     the operator one memory tier (dense/sharded ->
                     host-blocked -> memmap) carrying the warm iterate,
                     instead of failing the solve.  ``False`` re-raises
                     the OOM.
    """

    method: str = "block"
    eps: float = 1e-6
    max_iters: int = 200
    force_iters: bool = False
    warmup_q: int = 0
    oversample: int = 8
    sweep_dtype: str = "float32"
    n_blocks: int = 4
    block_rows: int = 1 << 16
    host_budget_bytes: int = 0
    seed: int = 0
    faithful: bool = False
    checkpoint_dir: Any = None
    checkpoint_every: int = 1
    on_iteration: Any = None
    io_retries: int = 3
    io_retry_backoff: float = 0.05
    health_retries: int = 3
    demote_on_oom: bool = True

    def __post_init__(self):
        # InputError subclasses ValueError, so pre-typed `except
        # ValueError` handlers keep catching config mistakes
        if self.method not in METHODS:
            raise InputError(f"unknown method {self.method!r}; expected "
                             f"one of {METHODS}")
        if self.eps <= 0:
            raise InputError(f"eps must be > 0, got {self.eps}")
        if self.max_iters < 1:
            raise InputError(f"max_iters must be >= 1, got {self.max_iters}")
        if self.warmup_q < 0:
            raise InputError(f"warmup_q must be >= 0, got {self.warmup_q}")
        if self.oversample < 0:
            raise InputError(
                f"oversample must be >= 0, got {self.oversample}")
        if self.n_blocks < 1:
            raise InputError(f"n_blocks must be >= 1, got {self.n_blocks}")
        if self.block_rows < 1:
            raise InputError(
                f"block_rows must be >= 1, got {self.block_rows}")
        if self.host_budget_bytes < 0:
            raise InputError(f"host_budget_bytes must be >= 0 (0 = "
                             f"unbounded), got {self.host_budget_bytes}")
        if self.checkpoint_every < 1:
            raise InputError(f"checkpoint_every must be >= 1, "
                             f"got {self.checkpoint_every}")
        if self.io_retries < 1:
            raise InputError(f"io_retries must be >= 1 (1 = no retry), "
                             f"got {self.io_retries}")
        if self.io_retry_backoff < 0:
            raise InputError(f"io_retry_backoff must be >= 0 seconds, "
                             f"got {self.io_retry_backoff}")
        if self.health_retries < 0:
            raise InputError(f"health_retries must be >= 0 (0 = fail on "
                             f"the first unhealthy step), "
                             f"got {self.health_retries}")
        if self.checkpoint_dir is not None and self.method != "block":
            raise InputError("checkpoint_dir requires method='block' "
                             "(only the block driver is a resumable "
                             "state machine)")
        if self.on_iteration is not None and self.method != "block":
            raise InputError("on_iteration requires method='block' "
                             "(the deflation engines have no per-"
                             "iteration SolverState to trace)")
        if self.warmup_q and self.method != "block":
            raise InputError("warmup_q > 0 requires method='block' "
                             "(deflation has no block iterate to "
                             "warm-start)")
        # canonicalize the dtype spelling (accepts jnp/np dtypes too)
        sd_name = resolve_sweep_dtype(self.sweep_dtype).name
        object.__setattr__(self, "sweep_dtype", sd_name)
        if sd_name != SWEEP_DTYPES[0] and self.method != "block":
            raise InputError("sweep_dtype != 'float32' requires "
                             "method='block' (only the block sweeps have "
                             "the mixed-precision policy; deflation stays "
                             "the fp32 oracle)")
        object.__setattr__(self, "seed", int(self.seed))

    def replace(self, **overrides: Any) -> "SVDConfig":
        """New config with ``overrides`` applied (re-validated)."""
        return dataclasses.replace(self, **overrides)

    def solver_fingerprint(self) -> str:
        """The trajectory-defining knobs, as a stable string.

        Two configs with the same fingerprint drive the block iterate
        through the SAME sequence of states from a given ``Q0``, so a
        checkpoint written under one may be resumed under the other.
        Budget/tolerance knobs (``eps``, ``max_iters``, ``force_iters``),
        the checkpoint/trace plumbing, and the recovery knobs
        (``io_retries``/``io_retry_backoff``/``health_retries``/
        ``demote_on_oom`` — retries replay identical work, never new
        work) are deliberately excluded — resuming a capped run with a
        larger budget or a different tolerance is the point of
        resumability.  ``n_blocks``/
        ``block_rows`` ARE included: they reorder the streamed FP
        accumulation, so a mismatch would break bitwise reproducibility.
        """
        return (f"method={self.method};warmup_q={self.warmup_q};"
                f"oversample={self.oversample};"
                f"sweep_dtype={self.sweep_dtype};n_blocks={self.n_blocks};"
                f"block_rows={self.block_rows};seed={self.seed}")


#: fixed tier keys a serialized ``SolverState`` records (absent = 0)
STATE_TIERS = ("disk", "host", "device")


@dataclasses.dataclass(frozen=True, eq=False)
class SolverState:
    """One block-driver iteration as a first-class, serializable value.

    The explicit state machine behind ``svd()`` (``core/svd.py``):
    ``init_state(op, k, cfg) -> SolverState``, ``step(op, state, cfg) ->
    SolverState`` (one ``gram_chain`` + orth + gap), ``finalize(op,
    state, cfg) -> SVDResult`` (Rayleigh–Ritz extract).  Everything the
    iteration loop used to trap in local variables lives here, which is
    what makes warm restarts (``svd_update``), checkpoint/resume
    (``checkpoint_dir=``), and per-iteration tracing (``on_iteration``)
    possible on every backend.

    ``Q``            the (N, l) subspace iterate, in the operator's
                     array namespace (host numpy once serialized).
    ``k``            target rank (``l >= k``; extraction truncates).
    ``it``           block iterations completed so far.
    ``prev_gap``/``gap``  the rotation-invariant subspace gaps driving
                     the (possibly lagged) convergence test.  May be
                     unsynced device scalars mid-run; floats once
                     serialized.  ``None`` = not yet measured.
    ``converged``    the criterion has been met (under ``lagged_sync``
                     this is decided one iteration late, so the state
                     already contains the bounded overshoot step).
    ``passes``       cumulative A-sized operand sweeps, across resumes:
                     each phase adds the operator-counter DELTA it
                     caused, so totals are conserved when a run is
                     killed and resumed in a fresh process.
    ``bytes_moved``  cumulative per-tier byte counters, same contract.
    ``config_fp``/``op_fp``  fingerprints of the trajectory-defining
                     config knobs and of the operator (backend, shape,
                     dtypes); resume refuses a checkpoint whose
                     fingerprints do not match the live run.
    """

    Q: Any
    k: int
    it: int = 0
    prev_gap: Any = None
    gap: Any = None
    converged: bool = False
    passes: int = 0
    bytes_moved: Any = None
    config_fp: str = ""
    op_fp: str = ""

    def replace(self, **overrides: Any) -> "SolverState":
        return dataclasses.replace(self, **overrides)

    # -- host serialization (CheckpointManager-compatible array tree) -------

    def to_tree(self, to_host=None) -> dict:
        """All-array pytree for ``CheckpointManager.save`` (fingerprints
        ride the manager's json meta, not the array tree).  ``to_host``
        is the operator's device->numpy hop for the iterate."""
        Qh = to_host(self.Q) if to_host is not None else self.Q
        gap = lambda v: np.asarray(
            np.nan if v is None else float(v), np.float64)
        tree = {
            "Q": np.asarray(Qh, np.float32),
            "k": np.asarray(self.k, np.int64),
            "it": np.asarray(self.it, np.int64),
            "prev_gap": gap(self.prev_gap),
            "gap": gap(self.gap),
            "converged": np.asarray(bool(self.converged)),
            "passes": np.asarray(int(self.passes), np.int64),
        }
        moved = self.bytes_moved or {}
        for tier in STATE_TIERS:
            tree[f"bytes_{tier}"] = np.asarray(
                int(moved.get(tier, 0)), np.int64)
        return tree

    @classmethod
    def from_tree(cls, tree, *, config_fp: str = "",
                  op_fp: str = "") -> "SolverState":
        """Inverse of ``to_tree``; ``Q`` stays host-side (the driver
        re-enters the operator namespace via ``op.from_host``)."""
        gap = lambda a: None if np.isnan(float(a)) else float(a)
        moved = {t: int(tree[f"bytes_{t}"]) for t in STATE_TIERS
                 if int(tree[f"bytes_{t}"])}
        return cls(Q=np.asarray(tree["Q"], np.float32),
                   k=int(tree["k"]), it=int(tree["it"]),
                   prev_gap=gap(tree["prev_gap"]), gap=gap(tree["gap"]),
                   converged=bool(tree["converged"]),
                   passes=int(tree["passes"]), bytes_moved=moved,
                   config_fp=config_fp, op_fp=op_fp)

    @classmethod
    def host_template(cls) -> dict:
        """A ``like`` tree for ``CheckpointManager.restore`` (dtypes
        only; array contents/shapes come from the checkpoint)."""
        z = lambda dt: np.zeros((), dt)
        tree = {"Q": np.zeros((0, 0), np.float32), "k": z(np.int64),
                "it": z(np.int64), "prev_gap": z(np.float64),
                "gap": z(np.float64), "converged": z(np.bool_),
                "passes": z(np.int64)}
        for tier in STATE_TIERS:
            tree[f"bytes_{tier}"] = z(np.int64)
        return tree


class SVDResult(NamedTuple):
    """Unified SVD result: ``A ~= U @ diag(S) @ V.T``.

    The first five fields are the legacy result-tuple fields, in the
    legacy order, so both attribute access (``res.S``) and positional
    slicing (``U, S, V = res[:3]``) written against the old per-backend
    NamedTuples keep working.  ``bytes_moved`` is a trailing defaulted
    field so 8-argument positional construction also keeps working.
    """

    U: Any                 # (m, k) left factor (row-sharded on "sharded")
    S: Any                 # (k,) singular values, descending
    V: Any                 # (n, k) right factor
    iters: Any             # (k,) iterations per rank (shared for "block")
    passes_over_A: Any     # A-sized operand sweeps / streams of the data
    bytes_per_pass: int    # bytes one pass moves at the configured dtype
    converged: bool        # criterion met before max_iters (False under
    #                        force_iters: the test is disabled)
    backend: str           # one of BACKENDS
    bytes_moved: Any = None  # per-tier total-byte breakdown for the
    #                          solve: {"disk": ..., "host": ...,
    #                          "device": ...} (tiers the backend touched;
    #                          ground truth from the operator's counters)
    faults: Any = None       # fault/recovery telemetry for the solve:
    #                          {"counters": {"<site>.<action>": n},
    #                          "events": [...]} from core/faults.py::
    #                          FaultTelemetry (block driver only; None
    #                          on the deflation engines)
    wall_time_s: Any = None  # end-to-end wall-clock seconds for the
    #                          svd() call (dispatch + solve + extract),
    #                          stamped once by the front door after the
    #                          factors are ready, so every
    #                          backend reports it and metering layers
    #                          (repro.serving) never clock the driver
    #                          from outside


def key_to_seed(key) -> int:
    """Recover the integer seed convention from a legacy jax PRNG key.

    ``PRNGKey(s)`` packs ``s`` into (hi, lo) uint32 words; folding them
    back gives the full 64-bit value, so ``seed_to_key(key_to_seed(k))``
    reproduces ``k`` exactly — including keys derived via ``split``/
    ``fold_in`` whose hi word has the top bit set (the deprecated
    ``tsvd`` shim's exact-translation contract).  ``None`` maps to the
    legacy default key ``PRNGKey(0)`` -> 0.  Integers pass through.
    """
    if key is None:
        return 0
    if isinstance(key, (int, np.integer)):
        return int(key)
    seed = 0
    for w in _key_words(key).ravel().tolist():
        seed = (seed << 32) | int(w)
    return seed


def _key_words(key) -> np.ndarray:
    """The raw uint32 words of a jax PRNG key (typed or legacy raw)."""
    import jax

    try:
        return np.asarray(jax.random.key_data(key))
    except (AttributeError, TypeError):  # raw uint32 key array
        return np.asarray(key)


def seed_to_key(seed: int):
    """The inverse: the jax PRNG key whose packed words equal ``seed``.

    For seeds below 2**32 under the default (2-word threefry) impl this
    IS ``PRNGKey(seed)``; anything wider — keys recovered from
    ``split``/``fold_in`` by ``key_to_seed``, or 4-word rbg-impl keys —
    is rebuilt word-for-word at the active impl's key width
    (``PRNGKey`` itself silently truncates wide seeds to 32 bits when
    x64 is disabled, so it cannot be used there).
    """
    import jax
    import jax.numpy as jnp

    n_words = _key_words(jax.random.PRNGKey(0)).size
    if n_words == 2 and 0 <= seed < (1 << 32):
        return jax.random.PRNGKey(seed)
    data = np.array([(seed >> (32 * (n_words - 1 - i))) & 0xFFFFFFFF
                     for i in range(n_words)], np.uint32)
    try:
        return jax.random.wrap_key_data(jnp.asarray(data))
    except AttributeError:  # old jax: raw uint32 arrays are the format
        return jnp.asarray(data)
