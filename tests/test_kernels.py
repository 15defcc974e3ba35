"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Shape/dtype sweeps per the assignment: every kernel asserts allclose
against its ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (block_gram_chain, block_gram_chain_ref,
                           block_matvec, block_matvec_ref, block_rmatvec,
                           block_rmatvec_ref, deflate_rmatvec,
                           deflate_rmatvec_ref, gram, gram_ref,
                           local_attention, local_attention_ref, matvec,
                           matvec_ref)


@pytest.mark.parametrize("m,n", [(128, 128), (256, 128), (384, 256),
                                 (130, 70), (512, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("symmetric", [True, False])
def test_gram_sweep(m, n, dtype, symmetric):
    rng = np.random.default_rng(m * 1000 + n)
    A = jnp.asarray(rng.normal(size=(m, n)), dtype)
    got = gram(A, bn=128, bk=128, symmetric=symmetric)
    want = gram_ref(A)
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol * float(jnp.abs(want).max()))


def test_gram_symmetric_equals_full():
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(256, 256)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(gram(A, symmetric=True, bn=128, bk=128)),
        np.asarray(gram(A, symmetric=False, bn=128, bk=128)), atol=1e-3)


@pytest.mark.parametrize("m,n", [(128, 128), (200, 300), (512, 130)])
def test_matvec_sweep(m, n):
    rng = np.random.default_rng(m + n)
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    got = matvec(A, v, bm=128, bn=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(matvec_ref(A, v)),
                               rtol=1e-4, atol=1e-3)


# k deliberately includes non-multiples of 128 (the TPU lane width):
# the ops wrappers must zero-pad the lane dimension and crop exactly —
# Mosaic rejects arbitrary k tiles on real TPU (regression for the
# missing-pad bug).
@pytest.mark.parametrize("m,n,k", [(256, 128, 4), (300, 200, 8),
                                   (128, 128, 64), (512, 130, 16),
                                   (256, 128, 130), (128, 256, 200)])
def test_block_matvec_sweep(m, n, k):
    rng = np.random.default_rng(m + n + k)
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    got = block_matvec(A, Q, bm=128, bn=128)
    assert got.shape == (m, k)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(block_matvec_ref(A, Q)),
                               rtol=1e-3, atol=1e-2)
    got = block_rmatvec(A, Y, bm=128, bn=128)
    assert got.shape == (n, k)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(block_rmatvec_ref(A, Y)),
                               rtol=1e-3, atol=1e-2)


# (512, 256, 40) and (640, 384, 13): several row tiles and column
# chunks, the benchmark's l = 40 and an l that is not a multiple of 8
@pytest.mark.parametrize("m,n,k", [(256, 128, 4), (300, 200, 8),
                                   (512, 130, 16), (256, 128, 130),
                                   (512, 256, 40), (640, 384, 13)])
def test_block_gram_chain_sweep(m, n, k):
    """One-sweep ``A^T (A Q)`` == oracle (block power / warm-start
    sweep), and off a float64 product by at most twice what XLA's
    HIGHEST two-dot chain is off on the same inputs."""
    rng = np.random.default_rng(m * 7 + n + k)
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    got = block_gram_chain(A, Q, bm=128, bn=128)
    assert got.shape == (n, k)
    want = block_gram_chain_ref(A, Q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=5e-2)
    A64, Q64 = np.asarray(A, np.float64), np.asarray(Q, np.float64)
    exact = A64.T @ (A64 @ Q64)
    with jax.default_matmul_precision("highest"):
        xla = np.asarray(jax.jit(lambda a, q: a.T @ (a @ q))(A, Q))
    err = np.linalg.norm(np.asarray(got) - exact)
    assert err <= 2 * np.linalg.norm(xla - exact)


@pytest.mark.parametrize("m,n,k", [(256, 128, 4), (300, 200, 130),
                                   (512, 256, 40), (640, 384, 13)])
@pytest.mark.parametrize("dtype", ["bfloat16", None])
def test_block_kernels_sweep_dtype(m, n, k, dtype):
    """The kernels' mixed-precision contract (sweep_dtype operands, fp32
    accumulation) matches the dtype-aware oracles — including with the
    lane-padded k.  Output is always fp32."""
    rng = np.random.default_rng(m + 13 * n + k)
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    Q = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))
    Y = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    for op, ref, rhs in ((block_matvec, block_matvec_ref, Q),
                         (block_rmatvec, block_rmatvec_ref, Y),
                         (block_gram_chain, block_gram_chain_ref, Q)):
        got = op(A, rhs, bm=128, bn=128, dtype=dtype)
        want = ref(A, rhs, dtype)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-2)
    # bf16 oracle differs from fp32 by the input rounding, not more:
    if dtype == "bfloat16":
        rel = (np.linalg.norm(np.asarray(block_gram_chain_ref(A, Q, dtype))
                              - np.asarray(block_gram_chain_ref(A, Q)))
               / np.linalg.norm(np.asarray(block_gram_chain_ref(A, Q))))
        assert 1e-5 < rel < 5e-2


def test_deflate_rmatvec_lane_padded_k():
    """Regression: deflate_rmatvec's (bm, k) U tiles put k on the lane
    axis; the wrapper must pad k to 128 and crop utxv back."""
    rng = np.random.default_rng(77)
    m, n, k = 256, 128, 130          # k > 128 and not a lane multiple
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    U = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    Xv = matvec_ref(A, jnp.asarray(rng.normal(size=(n,)).astype(np.float32)))
    SVtv = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    t13, utxv = deflate_rmatvec(A, U, Xv, SVtv, bm=128, bn=128)
    t13r, utxvr = deflate_rmatvec_ref(A, U, Xv, SVtv)
    assert utxv.shape == (k,)
    np.testing.assert_allclose(np.asarray(t13), np.asarray(t13r),
                               rtol=1e-3, atol=5e-2)
    np.testing.assert_allclose(np.asarray(utxv), np.asarray(utxvr),
                               rtol=1e-3, atol=5e-2)


def test_kernel_block_power_step_converges():
    """Full block subspace iteration built from the Pallas kernels."""
    rng = np.random.default_rng(11)
    A = rng.normal(size=(256, 128)).astype(np.float32)
    U, _, Vt = np.linalg.svd(A, full_matrices=False)
    s = np.zeros(128, np.float32)
    s[:3] = [10.0, 4.0, 1.0]
    A = (U * s) @ Vt
    Aj = jnp.asarray(A)
    Q = jnp.linalg.qr(
        jnp.asarray(rng.normal(size=(128, 3)).astype(np.float32)))[0]
    for _ in range(50):
        Z = block_rmatvec(Aj, block_matvec(Aj, Q, bm=128, bn=128),
                          bm=128, bn=128)
        Q, _ = jnp.linalg.qr(Z)
    W = np.asarray(block_matvec(Aj, Q, bm=128, bn=128))
    S = np.linalg.svd(W, compute_uv=False)
    np.testing.assert_allclose(S, [10.0, 4.0, 1.0], rtol=1e-3)


@pytest.mark.parametrize("m,n,k", [(256, 128, 4), (300, 200, 8), (128, 128, 1)])
def test_deflate_rmatvec_sweep(m, n, k):
    rng = np.random.default_rng(m + n + k)
    A = jnp.asarray(rng.normal(size=(m, n)).astype(np.float32))
    U = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    Xv = matvec_ref(A, jnp.asarray(rng.normal(size=(n,)).astype(np.float32)))
    SVtv = jnp.asarray(rng.normal(size=(k,)).astype(np.float32))
    t13, utxv = deflate_rmatvec(A, U, Xv, SVtv, bm=128, bn=128)
    t13r, utxvr = deflate_rmatvec_ref(A, U, Xv, SVtv)
    np.testing.assert_allclose(np.asarray(t13), np.asarray(t13r),
                               rtol=1e-3, atol=5e-2)
    np.testing.assert_allclose(np.asarray(utxv), np.asarray(utxvr),
                               rtol=1e-3, atol=5e-2)


def test_fused_deflated_step_equals_two_pass():
    """The kernel's fused sweep == the paper's two-pass Alg-4 schedule."""
    rng = np.random.default_rng(9)
    m, n, k = 256, 128, 4
    A = rng.normal(size=(m, n)).astype(np.float32)
    U, _ = np.linalg.qr(rng.normal(size=(m, k)).astype(np.float32))
    V, _ = np.linalg.qr(rng.normal(size=(n, k)).astype(np.float32))
    S = np.linspace(5, 1, k).astype(np.float32)
    v = rng.normal(size=(n,)).astype(np.float32)
    # faithful (paper Eq. 2, four separate terms)
    Xv = A @ v
    t1 = A.T @ Xv
    t2 = V @ (S * (U.T @ Xv))
    t3 = A.T @ (U @ (S * (V.T @ v)))
    t4 = V @ (S * S * (V.T @ v))
    v1_paper = t1 - t2 - t3 + t4
    # fused kernel
    SVtv = jnp.asarray(S * (V.T @ v))
    t13, utxv = deflate_rmatvec(jnp.asarray(A), jnp.asarray(U),
                                jnp.asarray(Xv), SVtv, bm=128, bn=128)
    v1_fused = (np.asarray(t13) - V @ (S * np.asarray(utxv))
                + V @ (S * S * (V.T @ v)))
    np.testing.assert_allclose(v1_fused, v1_paper, rtol=1e-3, atol=5e-2)


@pytest.mark.parametrize("B,H,Hkv,S,D,window", [
    (1, 4, 4, 128, 64, 64),     # MHA
    (2, 4, 2, 128, 64, 32),     # GQA
    (1, 8, 1, 256, 32, 256),    # MQA, window = S (full causal)
    (2, 2, 2, 192, 64, 48),     # non-pow2 seq
])
def test_local_attention_sweep(B, H, Hkv, S, D, window):
    rng = np.random.default_rng(B * 100 + S)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, Hkv, S, D)).astype(np.float32))
    got = local_attention(q, k, v, window=window, bq=64, bk=64)
    want = local_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_local_attention_softcap_and_bf16():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 4, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 128, 64)), jnp.bfloat16)
    got = local_attention(q, k, v, window=64, softcap=30.0, bq=64, bk=64)
    want = local_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), window=64, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=8, deadline=None)
@given(window=st.integers(1, 64), seed=st.integers(0, 100))
def test_property_window_monotone(window, seed):
    """Rows attend to exactly min(window, pos+1) keys -> window=S equals
    full causal attention; tiny windows approach identity over values."""
    rng = np.random.default_rng(seed)
    B, H, S, D = 1, 2, 64, 32
    q = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    got = local_attention(q, k, v, window=window, bq=32, bk=32)
    want = local_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
