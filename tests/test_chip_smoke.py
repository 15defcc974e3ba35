"""CPU checks of ``chip_smoke.py``'s logic at tiny sizes.

The script itself has no CPU mode (its ``main`` refuses to run without a
TPU); its phases take their sizes, so the same code runs here on the CPU
backend.  The four-device sharded phase runs in a faked-mesh child in
``tests/test_distributed.py``.
"""
import importlib.util
import os

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_planted_spectrum_has_the_gap(smoke):
    k = 8
    s = smoke.planted_spectrum(k)
    assert s.shape == (2 * k,) and s.dtype == np.float64
    assert np.all(np.diff(s) < 0)
    assert s[k] / s[k - 1] <= 0.3 + 1e-7


def test_planted_matrix_matches_float64_lapack(smoke):
    m, n, k = 512, 192, 8
    p = smoke.Planted(m, n, k, seed=3, noise_rel=1e-3)
    U = np.asarray(p.U, np.float64)
    np.testing.assert_allclose(U.T @ U, np.eye(2 * k), atol=1e-5)
    for A in (np.asarray(p.dense()), p.host(4)):
        sv = np.linalg.svd(A.astype(np.float64), compute_uv=False)
        # Weyl: the noise moves every sigma by at most its bound
        assert np.all(np.abs(sv[:2 * k] - p.s) <= 1e-5 * p.s[0] + p.bound)
        assert sv[2 * k] <= p.bound
        p.check_sigma(sv, 1e-5, "lapack")


def test_check_sigma_fails_loudly(smoke):
    p = smoke.Planted(256, 64, 4, seed=0)
    S = p.s[:4].copy()
    S[2] *= 1 + 1e-3
    with pytest.raises(smoke.SmokeError, match="off the planted"):
        p.check_sigma(S, 1e-4, "perturbed")


def test_phase_dense(smoke):
    k = 8
    p = smoke.Planted(512, 256, k, seed=0, noise_rel=1e-6)
    recs = smoke.phase_dense(p.dense(), p, k)
    assert [r["dtype"] for r in recs] == ["float32", "bfloat16"]
    for r in recs:
        assert r["backend"] == "dense" and r["converged"] and r["smoke"]
        assert r["sigma_max_rel_err"] <= r["sigma_tol"]
        assert r["passes_over_A"] == 2 * r["iters"] + 1


def test_phase_hostblocked(smoke):
    rec = smoke.phase_hostblocked(1024, 256, 8, 4, seed=1)
    assert rec["backend"] == "hostblocked" and rec["converged"]
    assert rec["bytes_moved"]["host"] == \
        rec["passes_over_A"] * 1024 * 256 * 4


def test_phase_hostblocked_names_the_ram_shortfall(smoke, monkeypatch):
    monkeypatch.setattr(smoke, "host_ram_bytes", lambda: (1 << 20, 1 << 21))
    with pytest.raises(smoke.SmokeError, match="short by"):
        smoke.phase_hostblocked(1024, 256, 8, 4, seed=1)


def test_phase_serving(smoke):
    rec = smoke.phase_serving(128, 64, 4, 4, (256, 128), seed=2)
    assert rec["batch_sizes"] == [4]
    assert rec["big_backend"] == "hostblocked"
    assert rec["sigma_max_rel_err"] <= rec["sigma_tol"]


def test_main_has_no_cpu_mode(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err
