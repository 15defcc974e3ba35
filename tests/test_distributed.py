"""Distributed behaviour via subprocesses (8 fake CPU devices).

The main pytest process must keep the single real device (per the dry-run
isolation rule), so every multi-device check runs in a child process with
its own XLA_FLAGS.  Checks are batched per subprocess to amortize startup.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str) -> str:
    env = dict(os.environ)
    # fake CPU devices: the child must never reach for the parent's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"child failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


DIST_SVD_CHECKS = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh as compat_make_mesh
from repro.core import dist_tsvd
mesh = compat_make_mesh((8,), ("data",))
rng = np.random.default_rng(0)
U0, _, Vt0 = np.linalg.svd(rng.normal(size=(128, 48)).astype(np.float32),
                           full_matrices=False)
s0 = np.linspace(20, 1, 48).astype(np.float32)
A = (U0 * s0) @ Vt0
for method in ["gram", "gramfree"]:
    for faithful in [True, False]:
        r = dist_tsvd(jnp.asarray(A), 4, mesh, method=method,
                      faithful=faithful, eps=1e-10, max_iters=500)
        np.testing.assert_allclose(np.asarray(r.S), s0[:4], rtol=2e-3), (
            method, faithful)
# wide input (CSVD orientation)
r = dist_tsvd(jnp.asarray(A.T), 4, mesh, eps=1e-10, max_iters=500)
np.testing.assert_allclose(np.asarray(r.S), s0[:4], rtol=2e-3)
# in-shard OOM batching (paper n_b)
r = dist_tsvd(jnp.asarray(A), 4, mesh, method="gramfree", n_blocks=4,
              eps=1e-10, max_iters=500)
np.testing.assert_allclose(np.asarray(r.S), s0[:4], rtol=2e-3)
# distributed U row-sharding is coherent: U^T U = I globally
r = dist_tsvd(jnp.asarray(A), 4, mesh, eps=1e-10, max_iters=500)
U = np.asarray(r.U)
np.testing.assert_allclose(U.T @ U, np.eye(4), atol=5e-3)
# two-axis distribution (pod x data)
mesh2 = compat_make_mesh((2, 4), ("pod", "data"))
r2 = dist_tsvd(jnp.asarray(A), 3, mesh2, axes=("pod", "data"),
               eps=1e-10, max_iters=500)
np.testing.assert_allclose(np.asarray(r2.S), s0[:3], rtol=2e-3)
# block subspace iteration: one fused (n, k) psum per step, all paths
r = dist_tsvd(jnp.asarray(A), 8, mesh, method="block", eps=1e-8,
              max_iters=500)
np.testing.assert_allclose(np.asarray(r.S), s0[:8], rtol=2e-3)
U = np.asarray(r.U)
np.testing.assert_allclose(U.T @ U, np.eye(8), atol=5e-3)
r = dist_tsvd(jnp.asarray(A.T), 4, mesh, method="block", eps=1e-8,
              max_iters=500)  # wide/CSVD orientation
np.testing.assert_allclose(np.asarray(r.S), s0[:4], rtol=2e-3)
r2 = dist_tsvd(jnp.asarray(A), 3, mesh2, axes=("pod", "data"),
               method="block", eps=1e-8, max_iters=500)
np.testing.assert_allclose(np.asarray(r2.S), s0[:3], rtol=2e-3)
# rank-deficient block: extras ~0 and every factor entry stays finite
s_def = np.zeros(48, np.float32); s_def[:4] = [9, 7, 5, 3]
A_def = (U0 * s_def) @ Vt0
r = dist_tsvd(jnp.asarray(A_def), 6, mesh, method="block", eps=1e-6,
              max_iters=300)
np.testing.assert_allclose(np.asarray(r.S)[:4], s_def[:4], rtol=2e-3)
assert np.all(np.asarray(r.S)[4:] < 1e-3 * s_def[0])
assert np.all(np.isfinite(np.asarray(r.U)))
assert np.all(np.isfinite(np.asarray(r.V)))
# range-finder warm start on REAL 8-way sharding: each shard sketches its
# own Omega row block; same answer, >= 3x fewer block iterations, and the
# pass accounting reflects the saving.
s_sep = np.zeros(48, np.float32)
s_sep[:16] = np.concatenate([np.linspace(20, 2, 8),
                             2 * 0.75 ** np.arange(1, 9)])
A_sep = (U0 * s_sep) @ Vt0
rc = dist_tsvd(jnp.asarray(A_sep), 8, mesh, method="block", eps=1e-6,
               max_iters=300)
rw = dist_tsvd(jnp.asarray(A_sep), 8, mesh, method="block", eps=1e-6,
               max_iters=300, warmup_q=1)
np.testing.assert_allclose(np.asarray(rw.S), s_sep[:8], rtol=2e-3)
np.testing.assert_allclose(np.asarray(rw.U).T @ np.asarray(rw.U),
                           np.eye(8), atol=5e-3)
assert int(rw.iters[0]) * 3 <= int(rc.iters[0]), (rw.iters, rc.iters)
assert int(rw.passes_over_A) < int(rc.passes_over_A)
print("DIST_SVD_OK")
"""


def test_distributed_svd_all_paths():
    assert "DIST_SVD_OK" in run_child(DIST_SVD_CHECKS)


SHARDED_TRAIN_CHECKS = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh as compat_make_mesh
from repro.models.config import ModelConfig
from repro.models import transformer as T
from repro import sharding as Sh
from repro.data import DataConfig, SyntheticLMDataset
from repro.training import TrainConfig, init_train_state, make_train_step
from repro.optim.adamw import AdamWConfig
from repro.optim.compression import CompressionConfig

mesh = compat_make_mesh((2, 4), ("data", "model"))
dc = DataConfig(vocab_size=64, seq_len=32, global_batch=8)
ds = SyntheticLMDataset(dc)

def train(cfg, steps=3):
    tc = TrainConfig(adamw=AdamWConfig(lr=1e-2))
    with Sh.use_mesh(mesh):
        state = init_train_state(jax.random.PRNGKey(0), cfg, tc)
        specs = Sh.tree_shardings(
            __import__("repro.training.train", fromlist=["train_state_specs"]
                       ).train_state_specs(cfg, tc), mesh)
        step = jax.jit(make_train_step(cfg, tc, mesh))
        losses = []
        for i in range(steps):
            state, m = step(state, ds.batch(i))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses), losses
        return losses

base = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
            d_ff=128, vocab_size=64, dtype="float32")
train(ModelConfig(name="d", family="dense", **base))
train(ModelConfig(name="m", family="moe", num_experts=4,
                  experts_per_token=2, **base))
train(ModelConfig(name="h", family="hybrid", num_layers=6,
                  block_pattern=("rglru", "rglru", "local"), window=8,
                  **{k: v for k, v in base.items() if k != "num_layers"}))
print("SHARDED_TRAIN_OK")

# multi-pod compressed-gradient training (the paper's technique crossing
# the pod axis) must equal... at least run and learn
mesh3 = compat_make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = ModelConfig(name="c", family="dense", **base)
tc = TrainConfig(adamw=AdamWConfig(lr=1e-2),
                 compression=CompressionConfig(enabled=True, rank=4,
                                               min_size=1024))
with Sh.use_mesh(mesh3):
    state = init_train_state(jax.random.PRNGKey(0), cfg, tc, mesh=mesh3)
    step = jax.jit(make_train_step(cfg, tc, mesh3))
    l0 = None
    for i in range(5):
        state, m = step(state, ds.batch(i))
        if l0 is None:
            l0 = float(m["loss"])
    assert float(m["compress_ratio"]) > 2
    assert np.isfinite(float(m["loss"]))
print("POD_COMPRESS_OK")
"""


def test_sharded_training_and_pod_compression():
    out = run_child(SHARDED_TRAIN_CHECKS)
    assert "SHARDED_TRAIN_OK" in out and "POD_COMPRESS_OK" in out


ELASTIC_CHECKS = r"""
import tempfile, numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh as compat_make_mesh
from repro.checkpoint import CheckpointManager
from repro.models.config import ModelConfig
from repro.models import transformer as T
from repro import sharding as Sh

cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=64,
                  dtype="float32")
params = T.init_model(jax.random.PRNGKey(0), cfg)
specs = T.model_specs(cfg)

mesh8 = compat_make_mesh((4, 2), ("data", "model"))
sh8 = Sh.tree_shardings(specs, mesh8,
                        jax.tree.map(lambda x: x.shape, params))
p8 = jax.device_put(params, sh8)

with tempfile.TemporaryDirectory() as d:
    mgr = CheckpointManager(d)
    mgr.save(3, p8)
    # elastic restore onto a DIFFERENT mesh shape (2x2 "new cluster")
    import numpy as onp
    devs = onp.array(jax.devices()[:4]).reshape(2, 2)
    mesh4 = jax.sharding.Mesh(devs, ("data", "model"))
    sh4 = Sh.tree_shardings(specs, mesh4,
                            jax.tree.map(lambda x: x.shape, params))
    restored = mgr.restore(3, params, shardings=sh4)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("ELASTIC_OK")
"""


def test_elastic_restore_different_mesh():
    assert "ELASTIC_OK" in run_child(ELASTIC_CHECKS)


HOST_INPUT_SHARDED_CHECKS = r"""
import sys
import numpy as np, jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.compat import make_mesh
from repro.core import svd

mesh = make_mesh((4,), ("data",), devices=jax.devices()[:4])
rng = np.random.default_rng(0)
for shape in ((256, 64), (64, 256)):       # tall, and wide (transposed in)
    U, _, Vt = np.linalg.svd(rng.normal(size=shape), full_matrices=False)
    s = np.geomspace(10, 0.1, min(shape))
    A = ((U * s) @ Vt).astype(np.float32)
    seen = {}

    def hook(state, op):
        seen["A"] = op._A
    hook._wants_operator = True

    res = svd(A, 4, mesh=mesh, on_iteration=hook, demote_on_oom=False)
    X, tall = seen["A"], (max(shape), min(shape))
    assert X.shape == tall, X.shape
    assert isinstance(X.sharding, NamedSharding), X.sharding
    assert X.sharding.spec == P("data", None), X.sharding.spec
    assert {d.id for d in X.sharding.device_set} == {0, 1, 2, 3}
    assert {sh.data.shape for sh in X.addressable_shards} == \
        {(tall[0] // 4, tall[1])}
    assert res.backend == "sharded" and res.converged
    assert np.asarray(res.U).shape == (shape[0], 4)
    assert np.asarray(res.V).shape == (shape[1], 4)
    np.testing.assert_allclose(np.asarray(res.S), s[:4], rtol=1e-4)
print("HOST_SHARDED_OK")

sys.path.insert(0, REPO)
import chip_smoke
rec = chip_smoke.phase_sharded(jax.devices()[:4], 1024, 256, 8, seed=0)
assert rec["backend"] == "sharded" and rec["converged"], rec
assert rec["devices"] == 4 and rec["slab_bytes"] == 1024 * 256
print("SMOKE_SHARDED_OK")
""".replace("REPO", repr(REPO))


def test_host_input_lands_sharded():
    """A numpy input with ``mesh=`` is placed straight onto the row
    shards (wide inputs transposed on the host first), and the chip
    smoke's sharded phase runs on a faked 4-device mesh."""
    out = run_child(HOST_INPUT_SHARDED_CHECKS)
    assert "HOST_SHARDED_OK" in out and "SMOKE_SHARDED_OK" in out
