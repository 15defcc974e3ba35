"""The dense backend's gram chain: which path runs, and what it counts.

``fused_chain_tiles`` picks the one-read Pallas chain from what the
operator can observe (platform, shapes, the iterate's width); the CPU
never takes it.  Tests that run the fused path on the CPU patch the
predicate, and the kernel runs in interpret mode.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DenseOperator, svd
from repro.core import operator as operator_mod

from conftest import make_lowrank

#: the slab cell's operand and iterate width (k = 32 plus 8 oversampled)
M, N, L = 65536, 32768, 40


@pytest.mark.parametrize("sweep_dtype", ["float32", "bfloat16"])
def test_tpu_and_tiling_shapes_take_the_fused_chain(sweep_dtype):
    assert operator_mod.fused_chain_tiles(
        "tpu", (M, N), L, sweep_dtype) == (256, 8192)


@pytest.mark.parametrize("platform,shape,l", [
    ("cpu", (M, N), L),                 # the kernel compiles on a TPU only
    ("gpu", (M, N), L),
    ("tpu", (M, N + 64), L),            # n % 128 != 0: A would be padded
    ("tpu", (M + 8, N), L),             # no whole row tiles
    ("tpu", (M, N), 1024),              # Q and Z outgrow VMEM
])
def test_other_cases_keep_the_xla_chain(platform, shape, l):
    assert operator_mod.fused_chain_tiles(
        platform, shape, l, "float32") is None


def test_cpu_operator_reads_a_twice_per_chain(rng):
    op = DenseOperator(jnp.asarray(rng.normal(size=(256, 128)),
                                   jnp.float32))
    assert op.chain_passes == 2
    op.gram_chain(jnp.ones((128, 8), jnp.float32))
    assert op.passes == 2


@pytest.fixture
def fused(monkeypatch):
    """The predicate says "fused" for any shape: 128-row tiles, 128-column
    chunks (the kernel runs in interpret mode on the CPU)."""
    monkeypatch.setattr(operator_mod, "fused_chain_tiles",
                        lambda platform, shape, l, sweep_dtype: (128, 128))


def test_fused_operator_counts_one_pass_per_chain(fused, rng):
    A = rng.normal(size=(384, 256)).astype(np.float32)
    Q = rng.normal(size=(256, 13)).astype(np.float32)
    op = DenseOperator(jnp.asarray(A))
    assert op.chain_passes == 1
    Z = op.gram_chain(jnp.asarray(Q))
    assert op.passes == 1
    assert op.bytes_moved == {"device": op.bytes_per_pass}
    want = A.astype(np.float64).T @ (A.astype(np.float64) @ Q)
    np.testing.assert_allclose(np.asarray(Z), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("sweep_dtype", ["float32", "bfloat16"])
def test_fused_svd_matches_the_xla_chain(sweep_dtype, monkeypatch, rng):
    A = jnp.asarray(make_lowrank(rng, 384, 256,
                                 [10.0, 6.0, 3.0, 1.5, 1.0, 0.5]))
    iters = 12
    kw = dict(method="block", force_iters=True, max_iters=iters,
              sweep_dtype=sweep_dtype, seed=3)
    ref = svd(A, 4, **kw)
    assert ref.passes_over_A == 2 * iters + 1
    monkeypatch.setattr(operator_mod, "fused_chain_tiles",
                        lambda platform, shape, l, sd: (128, 128))
    got = svd(A, 4, **kw)
    assert got.passes_over_A == iters + 1
    assert got.bytes_moved["device"] == (iters + 1) * got.bytes_per_pass
    np.testing.assert_allclose(np.asarray(got.S), np.asarray(ref.S),
                               rtol=1e-5 if sweep_dtype == "float32"
                               else 1e-3)
