"""The static contract checker catches known-bad step functions and
passes the real solver clean.

Each jaxpr/memory rule gets a deliberately-broken step function (two
psums, wrong payload, silent bf16 accumulation, f64 upcast, host
callback, oversized buffer) and the test asserts THAT rule — and only
that rule — fires.  The lint rules get minimal source snippets.  The
final tests run the full analyzer exactly as CI does and require a
clean report.
"""
import ast
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import run_all
from repro.analysis.allowlist import (ALLOWLIST, apply_allowlist,
                                      stale_entries)
from repro.analysis.jaxpr_check import (StepContract, check_step,
                                        collective_schedule, trace_jaxpr)
from repro.analysis.lint import lint_tree
from repro.analysis.memory import (check_memory, dot_read_bytes,
                                   peak_live_bytes)
from repro.analysis.report import AnalysisReport, CheckRecord, Violation
from repro.compat import make_mesh
from jax.sharding import PartitionSpec as P

N, K = 32, 4


def _mesh():
    return make_mesh((len(jax.devices()),), ("data",))


def _sharded(fn):
    mesh = _mesh()
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(P("data", None), P(None, None)),
                         out_specs=P(None, None))


def _rules(violations):
    return {v.rule for v in violations}


def _args(m_loc=16, dtype=jnp.float32):
    return (jax.ShapeDtypeStruct((m_loc, N), dtype),
            jax.ShapeDtypeStruct((N, K), jnp.float32))


ONE_PSUM = StepContract(psum_payloads=(((N, K),),))


# ---------------------------------------------------------------------------
# jaxpr pass: each contract rule fires on its known-bad step
# ---------------------------------------------------------------------------

def test_good_step_is_clean():
    @_sharded
    def step(A_loc, Q):
        return jax.lax.psum(A_loc.T @ (A_loc @ Q), "data")

    v, d = check_step(trace_jaxpr(step, *_args()), ONE_PSUM, "good")
    assert v == []
    assert d["n_psum"] == 1


def test_two_psums_fail_collective_count():
    @_sharded
    def step(A_loc, Q):
        AQ = jax.lax.psum(A_loc @ Q, "data")        # unfused half...
        return jax.lax.psum(A_loc.T @ AQ[:A_loc.shape[0]], "data")

    v, _ = check_step(trace_jaxpr(step, *_args()), ONE_PSUM, "two-psum")
    assert "collective-count" in _rules(v)


def test_wrong_payload_fails_collective_payload():
    @_sharded
    def step(A_loc, Q):
        # psum of the (m_loc, k) product instead of the (n, k) iterate
        return jax.lax.psum(A_loc @ Q, "data")

    v, _ = check_step(trace_jaxpr(step, *_args()), ONE_PSUM, "payload")
    assert "collective-payload" in _rules(v)


def test_stray_all_gather_fails():
    @_sharded
    def step(A_loc, Q):
        A_full = jax.lax.all_gather(A_loc, "data", tiled=True)
        return jax.lax.psum(A_loc.T @ (A_full[:A_loc.shape[0]] @ Q), "data")

    v, _ = check_step(trace_jaxpr(step, *_args()), ONE_PSUM, "gather")
    assert "stray-collective" in _rules(v)


def test_bf16_dot_without_preferred_type_fails():
    def step(A, Q):
        return A.astype(jnp.bfloat16) @ Q.astype(jnp.bfloat16)

    v, _ = check_step(trace_jaxpr(step, *_args()),
                      StepContract(requires_bf16=True), "bf16-bad")
    assert "bf16-accum" in _rules(v)


def test_bf16_dot_with_preferred_type_is_clean():
    def step(A, Q):
        return jax.lax.dot(A.astype(jnp.bfloat16), Q.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)

    v, d = check_step(trace_jaxpr(step, *_args()),
                      StepContract(requires_bf16=True), "bf16-good")
    assert v == []
    assert d["n_bf16_dots"] == 1


def test_fp32_trace_fails_requires_bf16():
    def step(A, Q):
        return A @ Q

    v, _ = check_step(trace_jaxpr(step, *_args()),
                      StepContract(requires_bf16=True), "no-bf16")
    assert "bf16-not-applied" in _rules(v)


def test_f64_upcast_fails():
    def step(A, Q):
        return (A @ Q).astype(jnp.float64)

    with jax.enable_x64(True):
        jx = trace_jaxpr(step, *_args())
    v, _ = check_step(jx, StepContract(), "f64")
    assert "f64-upcast" in _rules(v)


def test_host_callback_fails():
    def step(A, Q):
        out = A @ Q
        jax.debug.callback(lambda x: None, out)
        return out

    v, _ = check_step(trace_jaxpr(step, *_args()), StepContract(), "cb")
    assert "host-callback" in _rules(v)


def test_prng_key_avals_do_not_confuse_dtype_checks():
    # key<fry> avals coerce to float64 under np.dtype(); the checker
    # must not flag them (regression: random_* prims reported f64)
    def step(key):
        return jax.random.normal(key, (N, K), jnp.float32)

    v, _ = check_step(trace_jaxpr(step, jax.random.key(0)),
                      StepContract(), "key")
    assert v == []


def test_collective_schedule_reports_psum_bytes():
    @_sharded
    def step(A_loc, Q):
        return jax.lax.psum(A_loc.T @ (A_loc @ Q), "data")

    sched = collective_schedule(trace_jaxpr(step, *_args()))
    assert [c["prim"] for c in sched] == ["psum"]
    assert sched[0]["bytes"] == N * K * 4


# ---------------------------------------------------------------------------
# memory pass
# ---------------------------------------------------------------------------

def test_oversized_buffer_fails_budget():
    def step(A, Q):
        return A @ Q

    jx = trace_jaxpr(step, *_args())
    v, d = check_memory(jx, "big", budget_bytes=64)   # absurdly small
    assert _rules(v) == {"budget"}
    assert d["peak_live_bytes"] > 64

    v, _ = check_memory(jx, "fits", budget_bytes=1 << 30)
    assert v == []


def test_peak_live_bytes_counts_inputs_and_outputs():
    def step(A, Q):
        return A @ Q

    peak = peak_live_bytes(trace_jaxpr(step, *_args()))
    # A + Q + output all live at the dot: the floor is their sum
    assert peak >= (16 * N + N * K + 16 * K) * 4


def test_dot_read_bytes_counts_only_a_sized_operands():
    def step(A, Q):
        return A.T @ (A @ Q)        # two sweeps over A, two small dots

    a_nbytes = 16 * N * 4
    assert dot_read_bytes(trace_jaxpr(step, *_args()), a_nbytes) \
        == 2 * a_nbytes


def test_dot_read_bytes_counts_a_kernel_operand_once():
    """The one-read Pallas chain reads A as one kernel operand; the dots
    in its body run on blocks and never count."""
    from repro.kernels import ops

    def step(A, Q):
        return ops.block_gram_chain(A, Q, bm=128, bn=128, interpret=True)

    A = jax.ShapeDtypeStruct((384, 256), jnp.float32)
    Q = jax.ShapeDtypeStruct((256, 8), jnp.float32)
    assert dot_read_bytes(trace_jaxpr(step, A, Q), 384 * 256 * 4) \
        == 384 * 256 * 4


# ---------------------------------------------------------------------------
# lint pass
# ---------------------------------------------------------------------------

def _lint(src, relpath="core/fake.py"):
    return lint_tree(ast.parse(textwrap.dedent(src)), relpath)


def test_lint_flags_float_in_loop():
    v = _lint("""
        def drive(gaps):
            for g in gaps:
                if float(g) < 1e-6:
                    break
    """)
    assert _rules(v) == {"ANA001"}


def test_lint_sanctioned_sync_helper_is_clean():
    v = _lint("""
        def host_sync_scalar(x):
            while hasattr(x, "item"):
                x = x.item()
            return x
    """)
    assert v == []


def test_lint_flags_item_and_asarray_in_loop():
    v = _lint("""
        import numpy as np
        def drive(xs):
            for x in xs:
                y = x.item()
                z = np.asarray(x)
    """)
    assert len([x for x in v if x.rule == "ANA001"]) == 2


def test_lint_flags_frozen_state_mutation():
    v = _lint("""
        def advance(state):
            state.it = state.it + 1
    """)
    assert _rules(v) == {"ANA002"}


def test_lint_flags_raw_prngkey_outside_config():
    v = _lint("""
        import jax
        def sketch(seed):
            return jax.random.PRNGKey(seed)
    """)
    assert _rules(v) == {"ANA003"}
    assert _lint("""
        import jax
        def seed_to_key(seed):
            return jax.random.PRNGKey(seed)
    """, relpath="core/config.py") == []


def test_lint_flags_accounting_bypass():
    v = _lint("""
        def cheat(state):
            return state.replace(passes=0)
    """)
    assert "ANA004" in _rules(v)
    assert _lint("""
        def _stamp(state, d):
            return state.replace(passes=state.passes + d)
    """) == []


def test_lint_flags_uncached_jit_in_function():
    v = _lint("""
        import jax
        def step(A, Q):
            return jax.jit(lambda a, q: a @ q)(A, Q)
    """)
    assert _rules(v) == {"ANA005"}
    assert _lint("""
        import functools, jax
        @functools.lru_cache(maxsize=None)
        def step_fn(dtype):
            return jax.jit(lambda a, q: a @ q)
    """) == []


# ---------------------------------------------------------------------------
# allowlist + report plumbing
# ---------------------------------------------------------------------------

def test_apply_allowlist_marks_known_exception():
    key = next(iter(ALLOWLIST))
    target, rule = key.rsplit("::", 1)
    known = Violation("lint", rule, target, "msg")
    fresh = Violation("lint", "ANA001", "core/fake.py::f", "msg")
    out = apply_allowlist([known, fresh])
    assert out[0].allowlisted and out[0].reason == ALLOWLIST[key]
    assert not out[1].allowlisted


def test_stale_allowlist_entries_are_flagged():
    # no violations at all -> EVERY entry is stale
    stale = stale_entries([])
    assert {v.target for v in stale} == set(ALLOWLIST)
    assert all(v.rule == "stale-allowlist" for v in stale)


def test_report_json_shape():
    rep = AnalysisReport()
    rep.add([Violation("jaxpr", "collective-count", "t", "m")],
            CheckRecord("jaxpr", "t", "ok", {"n_psum": 2}))
    d = rep.to_dict()
    assert d["ok"] is False
    assert d["checks"][0]["pass_name"] == "jaxpr"
    assert d["violations"][0]["rule"] == "collective-count"
    rep2 = AnalysisReport()
    assert rep2.to_dict()["ok"] is True


# ---------------------------------------------------------------------------
# the real solver, exactly as CI runs it
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def full_report():
    return run_all()


def test_real_solver_passes_clean(full_report):
    assert full_report.ok, "\n".join(
        f"{v.target}::{v.rule}: {v.message}" for v in full_report.failures)


def test_real_run_covers_all_passes(full_report):
    seen = {c.pass_name for c in full_report.checks}
    assert {"jaxpr", "memory", "lint"} <= seen
    # every backend family shows up in the trace targets
    tags = {c.target for c in full_report.checks}
    for family in ("dense/", "sharded/", "hostblocked/", "memmap/",
                   "sparsestream/", "accounting:scipysparse",
                   "kernels/"):
        assert any(t.startswith(family) for t in tags), family


def test_real_run_accounting_groups_match(full_report):
    acct = [c for c in full_report.checks
            if c.target.startswith("accounting:")]
    assert acct, "accounting cross-checks missing"
    for c in acct:
        assert c.details["measured_bytes"] == c.details["expected_bytes"]


def test_real_run_counts_one_read_for_the_fused_dense_chain(full_report):
    from repro.analysis.targets import M, N_TILED
    acct = {c.target: c.details for c in full_report.checks}
    fused = acct["accounting:dense/chain/fused/float32"]
    assert fused["measured_bytes"] == fused["expected_bytes"] \
        == M * N_TILED * 4                      # one read of fp32 A
