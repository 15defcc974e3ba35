"""The single SVD front door: one solver, four execution regimes.

``svd(A, k, ...)`` dispatches on the input type — an in-memory jax
array, an array plus a mesh (row-sharded), a host numpy array or
``HostBlockedMatrix`` (out-of-core H2D streaming), a path /
``np.memmap`` / ``MemmapMatrix`` (disk tier: blocks staged disk->host->
device under a host budget), a ``scipy.sparse`` matrix (real CSR/COO
data on the fused sparse stream), a procedural sparse matrix (or any
duck-typed streamed operator), or a custom ``LinearOperator`` — and
runs ONE shared warm-start + block-iteration driver against the
``core/operator.py`` protocol.  The rank-one
deflation methods (``method="gram"``/``"gramfree"``, the paper's
Alg 1/2/4) remain available as per-backend engines behind the same
front door and the same ``SVDConfig``/``SVDResult`` types.

The block driver is the only copy of the solver logic, written as an
explicit three-phase state machine over a serializable ``SolverState``
(``core/config.py``) — the iteration, not the whole solve, is the unit
of failure and of warm restart:

* ``init_state(op, k, cfg)``: cold start ``Q0 = orth(random)``,
  randomized range-finder warm start ``Q0 = orth((A^T A)^q A^T Omega)``
  with ``k + oversample`` sketch columns (Halko-style; one
  ``range_sketch`` pass + ``q`` fused ``gram_chain`` refinements), a
  caller-supplied seed subspace (``svd_update`` — the previous factors
  aligned to the new shape, rank-b random append for new rows/cols), or
  an auto-resumed checkpoint (``cfg.checkpoint_dir``, fingerprints
  verified);
* ``step(op, state, cfg)``: ONE subspace iteration ``Q <- orth(A^T A
  Q)`` with the rotation-invariant subspace-gap test (sum of squared
  sines of principal angles — settles on clustered spectra where
  per-column tests never do), synced one iteration late on backends
  that ask for it (``lagged_sync`` — the H2D prefetch pipeline is never
  stalled; overshoot bounded at one pass).  Pure w.r.t. the operator:
  nothing is host-synced beyond what the lagged test already floats, so
  the jax backends keep the pipelined dispatch;
* ``finalize(op, state, cfg)``: Rayleigh–Ritz extraction via the
  operator (one more pass), truncating the oversampled columns.

``_run_block`` composes the three phases into the one-shot loop (its
results are bitwise-identical to the old closed loop — asserted in
tests), checkpointing the state through ``CheckpointManager`` and
invoking the ``cfg.on_iteration`` trace hook as it goes.  State
accounting is delta-based (each phase adds the operator-counter delta
it caused), so ``passes``/``bytes_moved`` totals are conserved when a
run is killed and resumed in a fresh process.

Pass accounting is the operator's own counter, so the reported
``passes_over_A`` is ground truth by construction (the instrumented-
operator tests assert it): dense/sharded sweeps cost 2 passes per
iteration, the streamed backends fuse both halves into 1.

The four legacy entrypoints (``tsvd``/``dist_tsvd``/``oom_tsvd``/
``sparse_tsvd``) are deprecated shims that translate their old keyword
spellings into an ``SVDConfig`` and delegate here (each warns once per
process); see the README migration table.
"""
from __future__ import annotations

import math
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.config import (SolverState, SVDConfig,  # noqa: F401
                               SVDResult, key_to_seed, seed_to_key)
from repro.core.errors import (FaultExhaustedError, InputError,
                               NumericalHealthError, SVDError,
                               is_oom_error)
from repro.core.faults import (FaultTelemetry, RetryPolicy, fault_hook,
                               maybe_corrupt)
from repro.core.operator import (DenseOperator, HostBlockedOperator,
                                 LinearOperator, ShardedOperator,
                                 SparseStreamOperator, host_sync_scalar,
                                 warm_start_width)
from repro.core.precision import fp32_dots, resolve_sweep_dtype
from repro.core.spans import span

__all__ = ["svd", "svd_update", "init_state", "step", "finalize",
           "SolverState", "SVDConfig", "SVDResult", "key_to_seed"]


# ---------------------------------------------------------------------------
# Deprecation bookkeeping for the legacy entrypoint shims
# ---------------------------------------------------------------------------

_LEGACY_WARNED: set[str] = set()


def warn_legacy(name: str) -> None:
    """Emit the one-per-process DeprecationWarning for a legacy shim."""
    if name in _LEGACY_WARNED:
        return
    _LEGACY_WARNED.add(name)
    warnings.warn(
        f"repro.core.{name}() is deprecated; call repro.core.svd(A, k, "
        f"config=SVDConfig(...)) instead (the old keywords map 1:1 onto "
        f"SVDConfig fields — see the README migration table)",
        DeprecationWarning, stacklevel=3)


def _reset_legacy_warnings() -> None:
    """Test hook: make every shim warn again."""
    _LEGACY_WARNED.clear()


# ---------------------------------------------------------------------------
# The shared block-iteration driver (the only copy of the solver),
# split into an explicit init/step/finalize state machine
# ---------------------------------------------------------------------------

def _tier_delta(before: dict, after: dict) -> dict:
    """Per-tier byte delta between two ``bytes_moved`` snapshots."""
    return {t: int(after[t]) - int(before.get(t, 0)) for t in after}


def _tier_merge(acc, delta: dict) -> dict:
    out = dict(acc or {})
    for t, v in delta.items():
        out[t] = out.get(t, 0) + v
    return out


def _stamp(state: SolverState, op: LinearOperator, p0: int,
           b0: dict, **updates) -> SolverState:
    """New state with the operator-counter deltas since (p0, b0) folded
    into the cumulative ``passes``/``bytes_moved`` accounting."""
    return state.replace(
        passes=state.passes + int(op.passes) - int(p0),
        bytes_moved=_tier_merge(state.bytes_moved,
                                _tier_delta(b0, dict(op.bytes_moved))),
        **updates)


def _tol(state: SolverState, cfg: SVDConfig) -> float:
    return cfg.eps * int(state.Q.shape[1])             # eps * l_eff


def init_state(op: LinearOperator, k: int, cfg: SVDConfig,
               warm=None, telemetry: FaultTelemetry | None = None
               ) -> SolverState:
    """Phase 1: build the initial iterate as a first-class SolverState.

    ``Q0`` comes from (in priority order) the latest matching checkpoint
    under ``cfg.checkpoint_dir`` (auto-resume — fingerprint mismatches
    error loudly), a caller-supplied host seed subspace ``warm`` (the
    ``svd_update`` path: aligned to the operator shape, random rank-b
    append for missing columns, then ``cfg.warmup_q`` fused
    refinements), the randomized range-finder sketch (``warmup_q > 0``),
    or a cold Gaussian block.
    """
    cfp = cfg.solver_fingerprint()
    ofp = op.fingerprint
    if cfg.checkpoint_dir is not None:
        state = _resume_state(op, k, cfg, cfp, ofp, telemetry=telemetry)
        if state is not None:
            return state
    p0, b0 = int(op.passes), dict(op.bytes_moved)
    N = op.shape[1]
    if warm is not None:
        Q = op.orth(op.from_host(_align_seed(warm, N, k, cfg)))
        for _ in range(cfg.warmup_q):                  # optional refinements
            Q = op.orth(op.gram_chain(Q))
    elif cfg.warmup_q > 0:
        l = warm_start_width(k, cfg.oversample, N)
        Q = op.orth(op.range_sketch(l, cfg.seed))      # sketch pass(es)
        for _ in range(cfg.warmup_q):                  # q refinements
            Q = op.orth(op.gram_chain(Q))
    else:
        Q = op.orth(op.random_block(k, cfg.seed))      # cold start: free
    return _stamp(SolverState(Q=Q, k=k, config_fp=cfp, op_fp=ofp),
                  op, p0, b0)


def _check_health(g: float, width: int, where: str) -> None:
    """The numeric health guard's test, applied to a SYNCED gap scalar.

    The gap is the one host-visible per-iteration scalar, and it is a
    perfect canary: any NaN/Inf anywhere in the iterate poisons the
    ``||Qn - Q Q^T Qn||_F^2`` reduction, and a finite value outside
    ``[0, l]`` means the bases stopped being orthonormal.  Before this
    guard a NaN gap silently never satisfied ``gap <= tol`` — the solve
    would burn ``max_iters`` on garbage and return NaN factors.
    """
    if not math.isfinite(g):
        raise NumericalHealthError(
            f"non-finite subspace gap ({g}) {where}: the iterate "
            f"contains NaN/Inf (overflowed sweep, corrupt input, or an "
            f"injected fault)", kind="nonfinite")
    if g < -1e-3 or g > width * 1.001 + 1e-3:
        raise NumericalHealthError(
            f"subspace gap {g} outside [0, {width}] {where}: "
            f"orthogonality loss in the iterate", kind="orth")


def step(op: LinearOperator, state: SolverState,
         cfg: SVDConfig) -> SolverState:
    """Phase 2: ONE subspace iteration — ``Q <- orth(A^T A Q)`` plus the
    convergence bookkeeping.  Pure w.r.t. the operator (one
    ``gram_chain``, one ``orth``, one ``subspace_gap``; the only host
    sync is the lagged ``float()`` of the PREVIOUS gap, dispatched after
    this iteration's work, so jax backends keep the pipelined
    dispatch with overshoot bounded at one pass over A).

    The synced gap doubles as the numeric health check: a NaN/Inf or
    out-of-range value raises ``NumericalHealthError`` instead of
    silently failing the ``<= tol`` test forever.  The driver loop
    catches it and rolls back to the last confirmed-healthy state;
    calling ``step`` directly surfaces the typed error.  Under
    ``force_iters`` nothing is synced, so nothing is checked (the
    benchmark mode trades the guard for zero host reads; ``finalize``
    still reports ``converged=False``).
    """
    tol = _tol(state, cfg)
    tel = getattr(op, "_telemetry", None)       # duck-typed operators
    fault_hook("device_oom", tel)               # chaos: OOM on dispatch
    p0, b0 = int(op.passes), dict(op.bytes_moved)
    with span("op.chain"):
        Z = op.gram_chain(state.Q)
    Z = maybe_corrupt("sweep", Z, tel)
    Qn = op.orth(Z)
    gap = op.subspace_gap(state.Q, Qn)  # device scalar on jax backends
    converged, prev_gap = False, state.prev_gap
    l = int(state.Q.shape[1])
    if not cfg.force_iters:            # paper's benchmark mode: no test
        if op.lagged_sync:
            # Sync the PREVIOUS gap: by the time the host read runs,
            # this iteration's stream is already dispatched, so the wait
            # can never stall the prefetch pipeline; overshoot is
            # bounded at one pass over A.
            if prev_gap is not None:
                g = host_sync_scalar(prev_gap)
                _check_health(g, l, f"at iteration {state.it}")
                if g <= tol:
                    converged = True   # this step WAS the overshoot
                else:
                    prev_gap = gap
            else:
                prev_gap = gap
        else:
            g = host_sync_scalar(gap)
            _check_health(g, l, f"at iteration {state.it + 1}")
            if g <= tol:
                converged = True
    return _stamp(state, op, p0, b0, Q=Qn, it=state.it + 1, gap=gap,
                  prev_gap=prev_gap, converged=converged)


def finalize(op: LinearOperator, state: SolverState,
             cfg: SVDConfig) -> SVDResult:
    """Phase 3: Rayleigh–Ritz extraction from the converged basis (one
    more pass), truncating the oversampled columns.  Factors live in the
    operator's array namespace; the per-backend assembly re-orients
    transposed inputs and may override the bookkeeping fields."""
    converged = state.converged
    if not converged and not cfg.force_iters and state.gap is not None:
        converged = bool(host_sync_scalar(state.gap) <= _tol(state, cfg))
    p0, b0 = int(op.passes), dict(op.bytes_moved)
    k = state.k
    with span("svd.extract"):
        U, S, V = op.extract(state.Q)                  # one more pass
        U, S, V = U[:, :k], S[:k], V[:, :k]            # drop oversampled
    iters = np.full((k,), state.it, np.int32)
    final = _stamp(state, op, p0, b0, converged=converged)
    return SVDResult(U, S, V, iters, int(final.passes), op.bytes_per_pass,
                     converged, op.backend, bytes_moved=final.bytes_moved)


def _align_seed(W, N: int, k: int, cfg: SVDConfig) -> np.ndarray:
    """Align a previous factor to the (N, l) iterate the operator needs.

    Rows: zero-pad for appended rows/cols of ``A`` (their directions
    re-enter through the very first ``gram_chain``), truncate for
    removed ones.  Columns: a seed already covering ``k`` directions is
    used AS-IS — appending fresh random columns would drag the subspace
    gap back to cold-start territory and forfeit the O(1)-iteration
    warm restart.  Only when ``k`` grew past the seed (rank-b append)
    are the missing directions filled with ``oversample`` extra
    seeded-Gaussian columns, so the new directions converge at the
    oversampled rate while the old ones stay converged.
    """
    W = np.asarray(W, np.float32)
    if W.ndim != 2:
        raise ValueError(f"warm seed must be 2-D, got shape {W.shape}")
    c = min(W.shape[1], N)
    l = c if c >= k else min(k + max(cfg.oversample, 0), N)
    out = np.zeros((N, l), np.float32)
    r = min(N, W.shape[0])
    out[:r, :c] = W[:r, :c]
    if l > c:
        rng = np.random.default_rng((int(cfg.seed) ^ 0x5EED) & (2**63 - 1))
        out[:, c:] = rng.standard_normal((N, l - c)).astype(np.float32)
    return out


def _resume_state(op, k, cfg, cfp: str, ofp: str,
                  telemetry: FaultTelemetry | None = None
                  ) -> SolverState | None:
    """Load the newest READABLE checkpointed SolverState, or None if the
    dir has none.  A corrupt/truncated step (a kill mid-write, a bad
    disk) is quarantined — renamed to ``step_X.corrupt`` — and resume
    falls back to the previous step instead of crashing; an INTACT step
    whose fingerprints/rank mismatch stays a hard error: silently
    restarting (or worse, continuing someone else's trajectory) would
    corrupt the pass accounting and the bitwise-reproducibility story."""
    from repro.checkpoint import CheckpointManager
    from repro.core.errors import CheckpointCorruptError
    mgr = CheckpointManager(cfg.checkpoint_dir)
    for step_no in reversed(mgr.all_steps()):
        try:
            extra = mgr.read_meta(step_no).get("extra", {})
            saved_cfp = extra.get("config_fp")
            saved_ofp = extra.get("op_fp")
            if saved_cfp != cfp or saved_ofp != ofp:
                raise InputError(
                    f"checkpoint_dir={cfg.checkpoint_dir!r} step "
                    f"{step_no} was written by a different run: config "
                    f"fingerprint {saved_cfp!r} vs {cfp!r}, operator "
                    f"fingerprint {saved_ofp!r} vs {ofp!r}; point "
                    f"checkpoint_dir at a fresh directory (or delete "
                    f"the stale steps) to start over")
            state = SolverState.from_tree(
                mgr.restore(step_no, SolverState.host_template()),
                config_fp=cfp, op_fp=ofp)
            if not np.all(np.isfinite(state.Q)):
                raise CheckpointCorruptError(
                    f"step {step_no}: non-finite iterate (the state was "
                    f"saved mid-corruption)")
        except CheckpointCorruptError as e:
            quarantined = mgr.quarantine(step_no)
            if telemetry is not None:
                telemetry.record("checkpoint", "quarantine",
                                 step=int(step_no), path=quarantined,
                                 error=str(e))
            continue                    # fall back to the previous step
        if state.k != k:
            raise InputError(
                f"checkpoint at {cfg.checkpoint_dir!r} targets rank "
                f"{state.k}, this call asked for rank {k}")
        return state.replace(Q=op.from_host(state.Q))
    return None


def _save_state(mgr, op, state: SolverState) -> None:
    mgr.save(state.it, state.to_tree(op.to_host),
             extra={"kind": "solver_state", "config_fp": state.config_fp,
                    "op_fp": state.op_fp})


def _carry_state(st: SolverState | None, op: LinearOperator,
                 telemetry: FaultTelemetry) -> SolverState | None:
    """Pull the warm iterate off a just-OOM'd operator so the demoted
    tier resumes from it instead of a cold start.  The cumulative
    ``passes``/``bytes_moved`` accounting rides along, so the reported
    totals stay conserved across the tier change.  If even the read-back
    fails (the device is truly wedged) the demotion falls back to a cold
    start and the telemetry records the lost iterate."""
    if st is None:
        return None
    try:
        # gap scalars belong to the old operator's stream; drop them so
        # the demoted tier re-measures convergence from its own sweeps
        return st.replace(Q=np.asarray(op.to_host(st.Q), np.float32),
                          gap=None, prev_gap=None)
    except Exception as e:             # noqa: BLE001 - device is gone
        telemetry.record("device_oom", "carry_failed", error=str(e))
        return None


def _drive(op: LinearOperator, k: int, cfg: SVDConfig, warm, mgr,
           telemetry: FaultTelemetry, carried: SolverState | None,
           cell: dict) -> SVDResult:
    """One tier's worth of the solve loop: init (or adopt the iterate
    carried down from a demoted tier), iterate with the numeric health
    guard, checkpoint on cadence, finalize.

    ``cell["state"]`` always holds the newest state so ``_run_block``
    can carry it across a device-OOM demotion.  A
    ``NumericalHealthError`` from ``step`` rolls the loop back to the
    last CONFIRMED-healthy state (``good``) and re-runs — the operator
    is deterministic, so a transient corruption (bit flip, injected
    fault) replays to the bitwise fault-free trajectory; the state's
    delta accounting resumes from ``good``, so the reported passes match
    the fault-free count and the physically discarded sweeps show up
    only in the fault telemetry.  ``cfg.health_retries`` consecutive
    failures raise ``FaultExhaustedError``.
    """
    if carried is not None:
        state = carried.replace(Q=op.from_host(carried.Q),
                                op_fp=op.fingerprint)
    else:
        state = init_state(op, k, cfg, warm=warm, telemetry=telemetry)
    cell["state"] = state
    good = state                        # last confirmed-healthy state
    health_attempts = 0
    last_saved = state.it if state.it else None         # resumed at it
    while True:
        if state.converged or state.it >= cfg.max_iters:
            # a run that exits on max_iters never synced its final gap:
            # surface NaN factors as a typed, recoverable error instead
            # of silently returning garbage with converged=False
            if (not cfg.force_iters and not state.converged
                    and state.gap is not None):
                try:
                    _check_health(host_sync_scalar(state.gap),
                                  int(state.Q.shape[1]),
                                  f"at iteration {state.it} (final)")
                except NumericalHealthError as err:
                    state, good, health_attempts = _recover(
                        op, cfg, err, good, health_attempts, telemetry)
                    cell["state"] = state
                    continue
            break
        p0 = int(op.passes)
        try:
            with span("svd.iter"):
                new = step(op, state, cfg)
        except NumericalHealthError as err:
            state, good, health_attempts = _recover(
                op, cfg, err, good, health_attempts, telemetry,
                discarded_passes=int(op.passes) - p0)
            cell["state"] = state
            continue
        # Track the newest CONFIRMED-healthy state: without lagged sync
        # the guard just checked `new` itself; with it, the synced gap
        # belonged to the parent, so only the parent is confirmed.
        if cfg.force_iters:
            good = new                  # benchmark mode: no guard at all
        elif not op.lagged_sync:
            good, health_attempts = new, 0
        elif state.prev_gap is not None:
            good, health_attempts = state, 0
        state = new
        cell["state"] = state
        if mgr is not None and state.it % cfg.checkpoint_every == 0:
            _save_state(mgr, op, state)                 # syncs the gap
            last_saved = state.it
        fault_hook("kill", telemetry)   # chaos: die AFTER the checkpoint
        if cfg.on_iteration is not None:
            # a hook marked `_wants_operator` (the serving runner's
            # partial-result streamer) also receives the live operator so
            # it can run an extra Rayleigh–Ritz extraction mid-solve;
            # plain hooks keep the one-argument trace signature
            if getattr(cfg.on_iteration, "_wants_operator", False):
                cfg.on_iteration(state, op)
            else:
                cfg.on_iteration(state)
    if mgr is not None and last_saved != state.it:
        _save_state(mgr, op, state)                     # final state
    return finalize(op, state, cfg)


def _recover(op, cfg, err: NumericalHealthError, good: SolverState,
             attempts: int, telemetry: FaultTelemetry,
             discarded_passes: int = 0):
    """Shared health-guard recovery: bounded rollback/re-orth to the
    last confirmed-healthy state, or ``FaultExhaustedError`` once
    ``cfg.health_retries`` consecutive recoveries failed to stick."""
    attempts += 1
    if attempts > cfg.health_retries:
        raise FaultExhaustedError(
            f"numeric health guard tripped {attempts} times in a row "
            f"({err}); rollback cannot recover — the input data or the "
            f"sweep_dtype={cfg.sweep_dtype!r} precision is unrecoverably "
            f"ill-conditioned (raise SVDConfig.health_retries only if "
            f"the corruption source is transient)") from err
    if err.kind == "orth":
        # the basis drifted off the Stiefel manifold: re-orthonormalize
        # in place (same subspace, clean Gram factors) and re-measure
        action = "reorth"
        state = good.replace(Q=op.orth(good.Q), gap=None, prev_gap=None)
    else:
        # NaN/Inf: the iterate is garbage — replay from the confirmed
        # state; the step is deterministic, so a transient corruption
        # retries onto the bitwise fault-free trajectory
        action = "rollback"
        state = good
    telemetry.record("health", action, it=int(good.it), kind=err.kind,
                     error=str(err), discarded_passes=int(discarded_passes))
    return state, good, attempts


def _run_block(op: LinearOperator, k: int, cfg: SVDConfig, warm=None):
    """init/step/finalize composed into the self-healing driver loop —
    bitwise-identical to the pre-state-machine closed loop on a healthy
    run (asserted in tests/test_solver_state.py) — plus the checkpoint
    writes and the ``on_iteration`` trace hook between steps.

    Resilience (the fault-tolerance layer, see ``core/faults.py``):

    * a per-solve ``FaultTelemetry`` + ``RetryPolicy`` is installed on
      the operator (``set_resilience``), so the staging hops retry
      transient I/O with bounded exponential backoff and every injected
      fault / recovery action lands in ``SVDResult.faults``;
    * ``NumericalHealthError`` from the step loop rolls back to the last
      confirmed-healthy state (``_drive``/``_recover``);
    * a device OOM (``RESOURCE_EXHAUSTED``) demotes down the memory
      ladder — dense/sharded -> host-blocked -> memmap — carrying the
      warm iterate and the cumulative pass/byte accounting, unless
      ``cfg.demote_on_oom`` is off.  The disk tier is the bottom: OOM
      there is terminal (``FaultExhaustedError``).
    """
    telemetry = FaultTelemetry()
    policy = RetryPolicy(max_attempts=cfg.io_retries,
                         base_delay=cfg.io_retry_backoff)
    mgr = None
    if cfg.checkpoint_dir is not None:
        from repro.checkpoint import CheckpointManager
        mgr = CheckpointManager(cfg.checkpoint_dir)
    carried = None
    # exclusive use of the operator for the whole solve (including
    # across tier demotions): the per-solve telemetry/retry install and
    # the pass/byte counters are per-operator mutable state, so two
    # concurrent solves sharing one instance would cross-wire their
    # accounting — a serving process fails the second job with a typed
    # error instead (see repro.serving)
    op.acquire_solve()
    try:
        while True:
            op.reset_counters()
            op.set_resilience(telemetry, policy)
            cell: dict = {"state": None}
            try:
                res = _drive(op, k, cfg, warm, mgr, telemetry, carried,
                             cell)
                return res._replace(faults=telemetry.snapshot())
            except Exception as e:
                if not (cfg.demote_on_oom and is_oom_error(e)):
                    if isinstance(e, SVDError):
                        # failed solves carry their fault/recovery
                        # telemetry too, so a serving layer can report
                        # WHY a job died (retries burned, demotions
                        # taken) without re-running it
                        e.faults = telemetry.snapshot()
                    raise
                new_op = op.demote(cfg)
                if new_op is None:
                    err = FaultExhaustedError(
                        f"device OOM on the {op.backend!r} backend with "
                        f"no lower tier to demote to; shrink the "
                        f"problem, lower n_blocks/host_budget_bytes "
                        f"pressure, or set demote_on_oom=False to see "
                        f"the raw error")
                    err.faults = telemetry.snapshot()
                    raise err from e
                carried = _carry_state(cell["state"], op, telemetry)
                telemetry.record(
                    "device_oom", "demote", frm=op.backend,
                    to=new_op.backend,
                    it=0 if carried is None else int(carried.it))
                new_op.acquire_solve()
                op.release_solve()
                op, warm = new_op, None  # carried iterate supersedes warm
    finally:
        op.release_solve()


def _deflation_converged(iters, cfg: SVDConfig) -> bool:
    """Conservative: True iff every rank stopped strictly before
    ``max_iters`` (the jitted deflation loops don't report their final
    `done` flag, so a rank meeting the criterion exactly on the last
    allowed iteration is indistinguishable from one that ran out)."""
    if cfg.force_iters:
        return False
    return bool(np.all(np.asarray(iters) < cfg.max_iters))


# ---------------------------------------------------------------------------
# Per-backend assembly
# ---------------------------------------------------------------------------

def _validate_problem(shape, k: int, source=None) -> None:
    """Reject degenerate problems with a typed, actionable error BEFORE
    any operator is built (a zero-dim matrix or an over-asked rank used
    to surface as a shape error deep inside a jitted sweep)."""
    m, n = int(shape[0]), int(shape[1])
    what = f" (from {source!r})" if source is not None else ""
    if m < 1 or n < 1:
        raise InputError(
            f"svd() input has shape {(m, n)}{what}: both dimensions must "
            f"be >= 1 — a zero-row/zero-column matrix has no singular "
            f"triplets to compute")
    if not isinstance(k, (int, np.integer)) or isinstance(k, bool):
        raise InputError(
            f"k must be a positive int, got {type(k).__name__} {k!r}")
    if k < 1:
        raise InputError(f"k must be >= 1, got {k}")
    if k > min(m, n):
        raise InputError(
            f"k={k} exceeds min(m, n)={min(m, n)} for input of shape "
            f"{(m, n)}{what}; a rank-{k} truncated SVD does not exist — "
            f"request at most min(m, n) triplets")


def _pick_seed(warm, transposed: bool):
    """The driver iterates in the tall orientation, so the seed subspace
    is the previous V — unless the input was transposed in, where the
    driver's right side is the previous U."""
    if warm is None:
        return None
    U_prev, V_prev = warm
    return U_prev if transposed else V_prev


def _dense_svd(A, k: int, cfg: SVDConfig, warm=None) -> SVDResult:
    A = jnp.asarray(A, jnp.float32)
    m, n = A.shape
    _validate_problem((m, n), k)
    bpp = m * n * jnp.dtype(cfg.sweep_dtype).itemsize
    if cfg.method == "block":
        tall = m >= n
        X = A if tall else A.T
        op = DenseOperator(X, sweep_dtype=cfg.sweep_dtype)
        res = _run_block(op, k, cfg, warm=_pick_seed(warm, not tall))
        if not tall:
            res = res._replace(U=res.V, V=res.U)
        return res._replace(bytes_per_pass=bpp)
    from repro.core.tsvd import _dense_deflation
    key = seed_to_key(cfg.seed)
    U, S, V, iters, passes = _dense_deflation(
        A, k, key, eps=cfg.eps, max_iters=cfg.max_iters,
        force_iters=cfg.force_iters, method=cfg.method)
    return SVDResult(U, S, V, np.asarray(iters), int(passes), bpp,
                     _deflation_converged(iters, cfg), "dense")


def _sharded_svd(A, k: int, mesh, axes, cfg: SVDConfig,
                 warm=None) -> SVDResult:
    if not isinstance(A, jax.Array):
        # host input: orient it on the host and let ShardedOperator put
        # each row slab straight onto its own device — never the whole
        # matrix on one chip first
        A = np.asarray(A)
    m, n = A.shape
    transposed = m < n                      # CSVD orientation: swap out
    if transposed:
        A = A.T
        m, n = n, m
    _validate_problem((m, n), k)
    bpp = m * n * jnp.dtype(cfg.sweep_dtype).itemsize
    if cfg.method == "block":
        if cfg.faithful:
            raise ValueError("method='block' has no paper-faithful "
                             "collective schedule (faithful=True applies "
                             "to the deflation methods)")
        # n_blocks is the OOM-staging / in-shard deflation-batching knob;
        # the block step is one fused matmat, so it has no batching here.
        op = ShardedOperator(A, mesh, axes, sweep_dtype=cfg.sweep_dtype)
        res = _run_block(op, k, cfg, warm=_pick_seed(warm, transposed))
        if transposed:
            res = res._replace(U=res.V, V=res.U)
        return res._replace(bytes_per_pass=bpp)
    from repro.core.dist_svd import _dist_deflation
    U, S, V, iters, passes = _dist_deflation(
        A, k, mesh, axes=axes, method=cfg.method,
        faithful=cfg.faithful, n_blocks=cfg.n_blocks, eps=cfg.eps,
        max_iters=cfg.max_iters, force_iters=cfg.force_iters,
        seed=cfg.seed)
    iters = np.asarray(iters)
    passes = int(passes)
    conv = _deflation_converged(iters, cfg)
    if transposed:
        U, V = V, U
    return SVDResult(U, S, V, iters, passes, bpp, conv, "sharded",
                     bytes_moved=None)  # jitted engine: no tier counters


def _hostblocked_svd(A, k: int, cfg: SVDConfig, warm=None) -> SVDResult:
    from repro.core.oom import HostBlockedMatrix, _oom_deflation
    sd = resolve_sweep_dtype(cfg.sweep_dtype)
    if isinstance(A, HostBlockedMatrix):
        if A.stage_dtype != sd:
            raise ValueError(
                f"injected operator staged as {A.stage_dtype.name} but "
                f"sweep_dtype={sd.name!r}; build the operator with "
                f"stage_dtype={sd.name!r}")
        host, transposed = A, False        # injected ops are already tall
    else:
        A_host = np.asarray(A)
        _validate_problem(A_host.shape, k)
        m, n = A_host.shape
        transposed = m < n
        if transposed:
            A_host = A_host.T
        host = HostBlockedMatrix(A_host, cfg.n_blocks, stage_dtype=sd)
    _validate_problem((host.m, host.n), k)
    if cfg.method == "block":
        op = HostBlockedOperator(host)
        res = _run_block(op, k, cfg, warm=_pick_seed(warm, transposed))
        if transposed:
            res = res._replace(U=res.V, V=res.U)
        return res._replace(bytes_per_pass=host.bytes_per_pass)
    elif cfg.method == "gramfree":
        U, S, V, iters, passes = _oom_deflation(
            host, k, eps=cfg.eps, max_iters=cfg.max_iters,
            force_iters=cfg.force_iters, seed=cfg.seed)
        conv = _deflation_converged(iters, cfg)
    else:
        raise ValueError("method='gram' is not available on the "
                         "out-of-core backend (the dense residual would "
                         "defeat the streaming); expected 'gramfree' | "
                         "'block'")
    if transposed:
        U, V = V, U
    return SVDResult(U, S, V, np.asarray(iters), passes,
                     host.bytes_per_pass, conv, "hostblocked",
                     bytes_moved=None)  # plain host matrices: no counters


def _memmap_svd(A, k: int, cfg: SVDConfig, warm=None) -> SVDResult:
    """Disk tier: ``A`` is a ``.npy`` path, an ``np.memmap``, or a
    pre-built ``MemmapMatrix`` — blocks are staged disk->host->device
    under ``cfg.host_budget_bytes`` of host cache."""
    from repro.core.diskio import MemmapMatrix
    from repro.core.oom import _oom_deflation
    from repro.core.operator import MemmapOperator
    sd = resolve_sweep_dtype(cfg.sweep_dtype)
    if isinstance(A, MemmapMatrix):
        if A.stage_dtype != sd:
            raise ValueError(
                f"injected operator staged as {A.stage_dtype.name} but "
                f"sweep_dtype={sd.name!r}; build the operator with "
                f"stage_dtype={sd.name!r}")
        host, transposed = A, False        # injected ops are already tall
    else:
        if isinstance(A, (str,)) or hasattr(A, "__fspath__"):
            from repro.core.diskio import open_matrix_memmap
            A = open_matrix_memmap(A)
        m, n = A.shape
        _validate_problem((m, n), k,
                          source=getattr(A, "filename", None))
        transposed = m < n                 # CSVD orientation: row-block
        src = A.T if transposed else A     # the tall view of the memmap
        host = MemmapMatrix(src, cfg.n_blocks, stage_dtype=sd,
                            host_budget_bytes=cfg.host_budget_bytes)
    _validate_problem((host.m, host.n), k)
    if cfg.method == "block":
        op = MemmapOperator(host)
        res = _run_block(op, k, cfg, warm=_pick_seed(warm, transposed))
        if transposed:
            res = res._replace(U=res.V, V=res.U)
        return res._replace(bytes_per_pass=host.bytes_per_pass)
    elif cfg.method == "gramfree":
        U, S, V, iters, passes = _oom_deflation(
            host, k, eps=cfg.eps, max_iters=cfg.max_iters,
            force_iters=cfg.force_iters, seed=cfg.seed)
        conv = _deflation_converged(iters, cfg)
    else:
        raise ValueError("method='gram' is not available on the disk "
                         "tier (the dense residual would defeat the "
                         "streaming); expected 'gramfree' | 'block'")
    if transposed:
        U, V = V, U
    # tier counters live on the matrix, so BOTH methods report the
    # actual disk/host/device breakdown
    return SVDResult(U, S, V, np.asarray(iters), passes,
                     host.bytes_per_pass, conv, "memmap",
                     bytes_moved=host.bytes_moved)


def _sparsestream_svd(sp, k: int, cfg: SVDConfig,
                      op_cls=SparseStreamOperator, warm=None) -> SVDResult:
    from repro.core.sparse import _sparse_deflation
    # duck-typed streamed sources expose either .shape or (.m, .n)
    shape = getattr(sp, "shape", None)
    if shape is None:
        shape = (getattr(sp, "m", 1), getattr(sp, "n", 1))
    _validate_problem(shape, k)
    if cfg.method == "block":
        op = op_cls(sp, block_rows=cfg.block_rows,
                    sweep_dtype=cfg.sweep_dtype)
        # sparse never transposes in, so the seed is always the prev V
        return _run_block(op, k, cfg, warm=_pick_seed(warm, False))
    elif cfg.method == "gramfree":
        U, S, V, iters, passes = _sparse_deflation(
            sp, k, eps=cfg.eps, max_iters=cfg.max_iters,
            force_iters=cfg.force_iters, seed=cfg.seed,
            block_rows=cfg.block_rows)
        conv = _deflation_converged(iters, cfg)
        # deflation is always fp32; one source of truth for the pass size
        bpp = op_cls(sp).bytes_per_pass
        moved = None            # the engine streams outside the operator
    else:
        raise ValueError("method='gram' is not available on the "
                         "sparse-streamed backend (the Gram matrix would "
                         "densify); expected 'gramfree' | 'block'")
    return SVDResult(U, S, V, np.asarray(iters), passes, bpp, conv,
                     op_cls.backend, bytes_moved=moved)


def _scipysparse_svd(sp, k: int, cfg: SVDConfig, warm=None) -> SVDResult:
    """Real scipy CSR/COO/CSC input on the fused sparse stream."""
    from repro.core.sparse import ScipySparseMatrix, ScipySparseOperator
    if not isinstance(sp, ScipySparseMatrix):
        sp = ScipySparseMatrix(sp, seed=cfg.seed)
    return _sparsestream_svd(sp, k, cfg, op_cls=ScipySparseOperator,
                             warm=warm)


#: dataset-file suffixes svd() accepts as path inputs
_PATH_SUFFIXES = (".npy", ".npz", ".mtx", ".mtx.gz")


def _path_svd(path, k: int, cfg: SVDConfig, warm=None) -> SVDResult:
    """Dispatch a dataset path: ``.npy`` -> disk tier (memmap), scipy
    ``.npz`` / MatrixMarket ``.mtx`` -> sparse stream."""
    import os
    import zipfile
    p = os.fspath(path)
    low = p.lower()
    if low.endswith(".npy"):
        return _memmap_svd(p, k, cfg, warm=warm)
    if low.endswith(".npz"):
        import scipy.sparse
        try:
            sp = scipy.sparse.load_npz(p)
        except (OSError, ValueError, KeyError, EOFError,
                zipfile.BadZipFile) as e:
            raise InputError(
                f"{p!r} is not a readable scipy-sparse .npz "
                f"({type(e).__name__}: {e}); re-save it with "
                f"scipy.sparse.save_npz or point svd() at an intact "
                f"file") from e
        return _scipysparse_svd(sp, k, cfg, warm=warm)
    if low.endswith((".mtx", ".mtx.gz")):
        import scipy.io
        try:
            sp = scipy.io.mmread(p).tocsr()
        except (OSError, ValueError, EOFError) as e:
            raise InputError(
                f"{p!r} is not a readable MatrixMarket file "
                f"({type(e).__name__}: {e}); re-export it with "
                f"scipy.io.mmwrite or point svd() at an intact file"
            ) from e
        return _scipysparse_svd(sp, k, cfg, warm=warm)
    raise InputError(
        f"svd() path input must end in one of {_PATH_SUFFIXES}, got {p!r}")


def _operator_svd(op: LinearOperator, k: int, cfg: SVDConfig,
                  warm=None) -> SVDResult:
    if cfg.method != "block":
        raise ValueError("custom LinearOperator inputs run the shared "
                         "block driver; method must be 'block'")
    _validate_problem(op.shape, k)
    op_sd = getattr(op, "sweep_dtype", cfg.sweep_dtype)
    if resolve_sweep_dtype(op_sd) != resolve_sweep_dtype(cfg.sweep_dtype):
        raise ValueError(
            f"operator was built with sweep_dtype={op_sd!r} but the "
            f"config says {cfg.sweep_dtype!r}; rebuild one of them")
    return _run_block(op, k, cfg, warm=_pick_seed(warm, False))


# ---------------------------------------------------------------------------
# Front door
# ---------------------------------------------------------------------------

def svd(A, k: int, *, mesh=None, axes=("data",),
        config: SVDConfig | None = None, _warm=None,
        **overrides) -> SVDResult:
    """Truncated SVD of ``A`` to rank ``k`` — the one entry point.

    Dispatch on the input type:

    * ``jax.Array``                         -> in-memory serial solve;
    * any array + ``mesh=``                 -> row-sharded over ``axes``
      of the mesh (one fused psum per A-sized product; wide inputs are
      transposed in and the factors swapped out);
    * ``np.ndarray``                        -> out-of-core: the array
      stays in host memory, split into ``n_blocks`` row blocks streamed
      H2D one at a time;
    * ``HostBlockedMatrix``                 -> out-of-core on a pre-built
      (possibly instrumented, possibly bf16-staged) host operator;
    * a path (``str``/``os.PathLike``)      -> dataset file: ``.npy`` is
      memory-mapped onto the disk tier, scipy ``.npz`` and MatrixMarket
      ``.mtx``/``.mtx.gz`` load onto the sparse stream;
    * ``np.memmap`` / ``MemmapMatrix``      -> disk tier: row blocks are
      staged disk->host->device on demand, the host cache capped at
      ``host_budget_bytes`` (so matrices larger than host RAM stream);
    * ``scipy.sparse`` CSR/COO/CSC          -> real sparse data on the
      fused streamed chains;
    * ``SyntheticSparseMatrix`` (or any object with the streamed
      ``matmat``/``rmatmat``/``gram_chain``/``range_sketch`` surface)
      -> sparse-streamed host solve;
    * a ``LinearOperator`` subclass         -> the shared block driver
      on your own backend.

    Solver knobs come from ``config`` (an ``SVDConfig``) and/or keyword
    ``overrides`` (applied on top of ``config`` and re-validated)::

        res = svd(A, 32, method="block", warmup_q=1, eps=1e-6)
        res = svd(A, 32, config=SVDConfig(sweep_dtype="bfloat16"),
                  mesh=mesh)

    Returns an ``SVDResult`` (U, S, V, iters, passes_over_A,
    bytes_per_pass, converged, backend, bytes_moved, faults,
    wall_time_s).
    """
    with span("svd.solve"):
        t0 = time.perf_counter()
        with fp32_dots():
            res = _dispatch(A, k, mesh=mesh, axes=axes, config=config,
                            _warm=_warm, **overrides)
        # one stamp at the front door covers every backend: metering
        # layers (repro.serving) read the wall clock off the result
        # instead of timing the driver from outside.  The lagged-sync
        # tiers return with work still queued, so the stamp waits for
        # the factors.
        jax.block_until_ready((res.U, res.S, res.V))
        return res._replace(wall_time_s=time.perf_counter() - t0)


def _dispatch(A, k: int, *, mesh=None, axes=("data",),
              config: SVDConfig | None = None, _warm=None,
              **overrides) -> SVDResult:
    import os
    cfg = config if config is not None else SVDConfig()
    if overrides:
        cfg = cfg.replace(**overrides)
    if _warm is not None and cfg.method != "block":
        raise ValueError("warm restarts (svd_update) seed the block "
                         "iterate; method must be 'block'")
    if mesh is not None:
        return _sharded_svd(A, k, mesh, tuple(axes), cfg, warm=_warm)
    if isinstance(A, LinearOperator):
        return _operator_svd(A, k, cfg, warm=_warm)
    if isinstance(A, jax.Array):
        return _dense_svd(A, k, cfg, warm=_warm)
    if isinstance(A, (str, os.PathLike)):
        return _path_svd(A, k, cfg, warm=_warm)
    if _is_scipy_sparse(A):
        return _scipysparse_svd(A, k, cfg, warm=_warm)
    # np.memmap subclasses np.ndarray and MemmapMatrix subclasses
    # HostBlockedMatrix: the disk-tier checks must come FIRST.
    if isinstance(A, np.memmap):
        return _memmap_svd(A, k, cfg, warm=_warm)
    if isinstance(A, np.ndarray):
        return _hostblocked_svd(A, k, cfg, warm=_warm)
    from repro.core.diskio import MemmapMatrix
    from repro.core.oom import HostBlockedMatrix
    if isinstance(A, MemmapMatrix):
        return _memmap_svd(A, k, cfg, warm=_warm)
    if isinstance(A, HostBlockedMatrix):
        return _hostblocked_svd(A, k, cfg, warm=_warm)
    from repro.core.sparse import ScipySparseMatrix
    if isinstance(A, ScipySparseMatrix):
        return _scipysparse_svd(A, k, cfg, warm=_warm)
    if all(hasattr(A, attr) for attr in
           ("matmat", "rmatmat", "gram_chain", "range_sketch")):
        return _sparsestream_svd(A, k, cfg, warm=_warm)
    raise InputError(
        f"svd() cannot dispatch on input of type {type(A).__name__}: "
        "expected a jax array (serial), an array plus mesh= (sharded), "
        "a numpy array or HostBlockedMatrix (out-of-core), a .npy/.npz/"
        ".mtx path, np.memmap, or MemmapMatrix (disk tier), a "
        "scipy.sparse matrix or streamed sparse operator, or a "
        "LinearOperator")


def svd_update(prev, A, k: int | None = None, *, mesh=None,
               axes=("data",), config: SVDConfig | None = None,
               **overrides) -> SVDResult:
    """Re-decompose a perturbed ``A`` warm-started from a previous solve.

    ``prev`` is the ``SVDResult`` of an earlier ``svd()`` on a nearby
    matrix (small dense delta, appended rows/columns, grown rank) — or a
    live/checkpointed ``SolverState``.  The block iterate is seeded with
    the previous right factors instead of a Gaussian sketch (aligned to
    the new shape: zero rows for appended rows/cols, a seeded random
    rank-b append plus ``oversample`` columns when the subspace must
    grow), so the update converges in O(1) block iterations where a
    cold start needs tens (``benchmarks/update.py`` measures this).

    ``k`` defaults to the previous rank.  Everything else — backend
    dispatch on ``A``'s type, ``mesh=``, ``config``/``overrides`` —
    works exactly as in ``svd()``; ``method`` must be ``'block'``.
    """
    if isinstance(prev, SolverState):
        Q = np.asarray(jax.device_get(prev.Q), np.float32)
        warm = (Q, Q)     # the iterate is already the tall right side
        if k is None:
            k = int(prev.k)
    elif isinstance(prev, SVDResult):
        warm = (np.asarray(jax.device_get(prev.U), np.float32),
                np.asarray(jax.device_get(prev.V), np.float32))
        if k is None:
            k = int(np.asarray(prev.S).shape[0])
    else:
        raise TypeError(
            f"svd_update() seeds from a previous SVDResult or "
            f"SolverState, got {type(prev).__name__}")
    return svd(A, k, mesh=mesh, axes=axes, config=config, _warm=warm,
               **overrides)


def _is_scipy_sparse(A) -> bool:
    """True iff ``A`` is a scipy sparse matrix/array (scipy optional)."""
    try:
        import scipy.sparse
    except ImportError:  # pragma: no cover - scipy is optional
        return False
    return scipy.sparse.issparse(A)
