"""Range-finder warm start across all four t-SVD paths, pass-accounting
cross-checks, and regressions for this PR's bugfixes (XLA_FLAGS clobber,
OOMResult iters, empty sparse row blocks, batched convergence checks)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.compat import make_mesh
from repro.core import (CountingHostMatrix, DenseStreamOperator,
                        SyntheticSparseMatrix, dist_tsvd, oom_tsvd,
                        sparse_tsvd, tsvd)

from conftest import make_lowrank

# the benchmark owns the spectra so its reported numbers and this file's
# assertions always describe the same problems
from benchmarks.warmstart import (OVERSAMPLE, clustered_spectrum,
                                  separated_spectrum)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Acceptance: warmup_q=1 cuts block iterations >= 3x on all four paths
# ---------------------------------------------------------------------------

def test_warm_start_3x_fewer_iters_all_four_paths(rng):
    """512x256 rank-32, separated spectrum: warm start must converge in
    >= 3x fewer block iterations (and fewer passes over A) on the serial,
    distributed, out-of-core, and streamed-sparse paths — asserted via
    the uniform pass accounting."""
    k = 32
    A = make_lowrank(rng, 512, 256, separated_spectrum(k))
    s_np = np.linalg.svd(A, compute_uv=False)[:k]
    Aj = jnp.asarray(A)
    mesh = make_mesh((1,), ("data",))
    op = DenseStreamOperator(A)

    def measure(q):
        out = {}
        out["serial"] = tsvd(Aj, k, jax.random.PRNGKey(0), method="block",
                             eps=1e-6, max_iters=300, warmup_q=q,
                             oversample=OVERSAMPLE)
        out["dist"] = dist_tsvd(Aj, k, mesh, method="block", eps=1e-6,
                                max_iters=300, warmup_q=q,
                                oversample=OVERSAMPLE)
        out["oom"] = oom_tsvd(A, k, n_blocks=4, method="block", eps=1e-6,
                              max_iters=300, warmup_q=q,
                              oversample=OVERSAMPLE)
        out["sparse"] = sparse_tsvd(op, k, method="block", eps=1e-6,
                                    max_iters=300, warmup_q=q,
                                    oversample=OVERSAMPLE)
        for path, r in out.items():
            np.testing.assert_allclose(np.asarray(r.S), s_np, rtol=1e-3,
                                       err_msg=f"{path} q={q}")
        return out

    cold, warm = measure(0), measure(1)
    for path in cold:
        ci, cp = int(cold[path].iters[0]), int(cold[path].passes_over_A)
        wi, wp = int(warm[path].iters[0]), int(warm[path].passes_over_A)
        assert wi * 3 <= ci, f"{path}: warm {wi} vs cold {ci} iters"
        assert wp < cp, f"{path}: warm {wp} vs cold {cp} passes"


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_property_clustered_spectrum_warm_beats_cold_10x(seed):
    """Clustered spectrum: warm start converges in a small constant
    number of sweeps where the cold start needs ~10x as many."""
    rng = np.random.default_rng(seed)
    k = 8
    A = make_lowrank(rng, 128, 64, clustered_spectrum(k))
    kw = dict(method="block", eps=1e-6, max_iters=300,
              oversample=OVERSAMPLE)
    cold = tsvd(jnp.asarray(A), k, jax.random.PRNGKey(0), **kw)
    warm = tsvd(jnp.asarray(A), k, jax.random.PRNGKey(0), warmup_q=1, **kw)
    wi, ci = int(warm.iters[0]), int(cold.iters[0])
    assert wi <= 3
    assert ci >= 10
    assert wi * 5 <= ci
    s_np = np.linalg.svd(A, compute_uv=False)[:k]
    np.testing.assert_allclose(np.asarray(warm.S), s_np, rtol=1e-3)


def test_warm_start_wide_orientation(rng):
    """CSVD orientation: warm start + truncation keep factor shapes."""
    A = make_lowrank(rng, 64, 160, np.linspace(12, 2, 10))
    res = tsvd(jnp.asarray(A), 5, jax.random.PRNGKey(0), method="block",
               eps=1e-8, max_iters=300, warmup_q=1)
    assert res.U.shape == (64, 5) and res.V.shape == (160, 5)
    s_np = np.linalg.svd(A, compute_uv=False)[:5]
    np.testing.assert_allclose(np.asarray(res.S), s_np, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(res.V.T @ res.V), np.eye(5),
                               atol=5e-3)


def test_warmup_requires_block_method(rng):
    A = make_lowrank(rng, 32, 16, [5.0, 1.0])
    with pytest.raises(ValueError, match="block"):
        tsvd(jnp.asarray(A), 2, method="gram", warmup_q=1)
    with pytest.raises(ValueError, match="block"):
        oom_tsvd(A, 2, method="gramfree", warmup_q=1)
    with pytest.raises(ValueError, match="block"):
        sparse_tsvd(DenseStreamOperator(A), 2, method="gramfree",
                    warmup_q=1)


# ---------------------------------------------------------------------------
# Pass accounting: reported counts == instrumented operator counts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method,kwargs", [
    ("block", {}),
    ("block", {"warmup_q": 1}),
    ("block", {"warmup_q": 2, "oversample": 4}),
    ("gramfree", {}),
])
def test_oom_reported_passes_match_instrumented_operator(rng, method,
                                                         kwargs):
    """Regression: OOMResult now carries iters + passes_over_A, and the
    analytic accounting must equal what the streamed operator actually
    fetched (the cross-check the benchmarks rely on)."""
    A = make_lowrank(rng, 120, 48, np.linspace(12, 2, 8))
    op = CountingHostMatrix(A, 3)
    res = oom_tsvd(None, 6, op=op, method=method, eps=1e-8, max_iters=60,
                   **kwargs)
    assert res.iters.shape == (6,)
    assert int(res.iters[0]) >= 1
    assert res.passes_over_A == op.passes, (
        f"reported {res.passes_over_A} != counted {op.passes}")
    s_np = np.linalg.svd(A, compute_uv=False)[:6]
    np.testing.assert_allclose(np.asarray(res.S), s_np, rtol=2e-3)


def test_serial_pass_accounting_formulas(rng):
    """The serial methods report the documented _PASS_ACCOUNTING sums."""
    A = make_lowrank(rng, 96, 40, np.linspace(12, 2, 8))
    k = 4
    r = tsvd(jnp.asarray(A), k, jax.random.PRNGKey(0), method="gram",
             eps=1e-8, max_iters=300)
    assert int(r.passes_over_A) == 3 * k
    r = tsvd(jnp.asarray(A), k, jax.random.PRNGKey(0), method="gramfree",
             eps=1e-8, max_iters=300)
    assert int(r.passes_over_A) == 3 * int(np.sum(np.asarray(r.iters))) + k
    r = tsvd(jnp.asarray(A), k, jax.random.PRNGKey(0), method="block",
             eps=1e-8, max_iters=300)
    assert int(r.passes_over_A) == 2 * int(r.iters[0]) + 1
    r = tsvd(jnp.asarray(A), k, jax.random.PRNGKey(0), method="block",
             eps=1e-8, max_iters=300, warmup_q=2)
    assert int(r.passes_over_A) == (1 + 2 * 2) + 2 * int(r.iters[0]) + 1


# ---------------------------------------------------------------------------
# Bugfix regressions
# ---------------------------------------------------------------------------

def test_row_block_coo_empty_range():
    """Regression: an empty row range (trailing empty block of a plan)
    used to raise from np.concatenate([]); it must yield empty arrays."""
    sp = SyntheticSparseMatrix(m=256, n=64, nnz_per_row=4, seed=0, chunk=64)
    rows, cols, vals = sp.row_block_coo(128, 128)
    assert rows.size == 0 and cols.size == 0 and vals.size == 0
    assert rows.dtype == np.int64 and vals.dtype == np.float32
    assert sp.row_block_dense(17, 17).shape == (0, 64)
    # hi < lo (degenerate plan) is also safe
    r2, c2, v2 = sp.row_block_coo(60, 40)
    assert r2.size == 0 and c2.size == 0 and v2.size == 0


def test_oom_gramfree_batched_convergence_still_converges(rng):
    """Regression for the per-iteration bool(done) device sync: the
    batched check may overshoot by at most CHECK_EVERY - 1 iterations
    and must not change the factorization."""
    from repro.core.oom import CONVERGENCE_CHECK_EVERY
    A = make_lowrank(rng, 96, 32, np.linspace(9, 3, 4))
    res = oom_tsvd(A, 2, n_blocks=3, eps=1e-10, max_iters=500)
    s_np = np.linalg.svd(A, compute_uv=False)[:2]
    np.testing.assert_allclose(np.asarray(res.S), s_np, rtol=2e-3)
    # every reported count lands on a check boundary (or max_iters)
    for it in np.asarray(res.iters):
        assert it % CONVERGENCE_CHECK_EVERY == 0 or it == 500


def test_svd_dryrun_appends_to_existing_xla_flags():
    """Regression: importing launch.svd_dryrun (and launch.dryrun) used
    to overwrite XLA_FLAGS, clobbering user/CI-provided flags."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # never the parent's chip
    env["XLA_FLAGS"] = "--xla_dump_to=/tmp/xla_dump_regression_test"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = ("import os\n"
            "import repro.launch.svd_dryrun\n"
            "import repro.launch.dryrun\n"
            "print(os.environ['XLA_FLAGS'])\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    flags = out.stdout.strip().splitlines()[-1].split()
    assert "--xla_dump_to=/tmp/xla_dump_regression_test" in flags
    assert flags.count("--xla_force_host_platform_device_count=512") == 1


def test_with_xla_flag_helper_is_idempotent():
    # xla_flags deliberately has no import side effects (unlike the
    # dry-run modules, which append the 512-device flag at import)
    from repro.launch.xla_flags import with_xla_flag
    flag = "--xla_force_host_platform_device_count=512"
    assert with_xla_flag(None, flag) == flag
    assert with_xla_flag("", flag) == flag
    assert with_xla_flag("--xla_foo=1", flag) == f"--xla_foo=1 {flag}"
    assert with_xla_flag(f"--xla_foo=1 {flag}", flag) == f"--xla_foo=1 {flag}"
