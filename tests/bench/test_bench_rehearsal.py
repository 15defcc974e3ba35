"""A tiny CPU rehearsal of the ``solve-planted`` cells through the
harness's own functions (everything ``bench/run.py`` does after its
look for a chip), on each configuration's backend: ``dense``,
``hostblocked``, and ``sharded`` on four fake CPU devices in a child
process (the row-sharded ``fig3a-node-4chip`` layout under the slab
cell's traffic and limits; that configuration has no cell yet).  The
comparison passes the program, reads the control well above it, and
comes out not correct when the timed path is broken underneath: a sweep
that returns its iterate unchanged, half of the rows left out (the rest
counted twice), the psum between chips left out, a singular value
altered where the extraction makes it, the trailing triplets of the
extraction returned, exact triplets returned in ascending order, and a
solve that stops early, is demoted or records a fault.
"""
import json
import os
import subprocess
import sys
import time

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

#: tiny shapes of each cell: the configuration's tier and dtypes, with
#: rows, columns, rank, blocks and iterations cut to what a test can hold
TINY = {"slab.solve": dict(m=1024, n=256, k=8, iters=12),
        "hoststream.solve": dict(m=1024, n=256, k=8, n_blocks=4, iters=12),
        "fig3a-node-4chip": dict(m=1024, n=256, k=8, iters=12)}
SEED = 3_000_000_019      # past 2**31, as a run's --seed may be


def _cell(name, **over):
    cell = harness.load_cell(name)
    cell["config"].update(TINY[name], **over)
    return cell


def _run(cell, solver="program", trace=False, devices=None, seconds=0.3):
    return harness.run_cell(cell, SEED, seconds, trace,
                            devices or jax.devices()[:1],
                            time.perf_counter(), solver=solver)


@pytest.mark.parametrize("name", ["slab.solve", "hoststream.solve"])
def test_the_program_passes_the_comparison(name):
    res = _run(_cell(name))
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {m["name"] for m in
                                   _cell(name)["end_to_end"]}
    assert list(res)[-1] == "check"
    for v in res["check"].values():
        assert v["value"] <= v["limit"]


def test_a_traced_run_reads_the_counters():
    res = _run(_cell("hoststream.solve"), trace=True)
    assert res["correct"]
    m = res["metrics"]
    assert m["iters_per_solve.stream"]["value"] >= 1
    assert (m["passes_per_solve.stream"]["value"]
            == m["iters_per_solve.stream"]["value"] + 1)
    assert m["h2d_GBps"]["value"] > 0
    # no device plane on the CPU: nothing to read, never a 0
    assert "device_idle.stream" not in m and "sweep_roofline.stream" not in m
    assert res["device"]["window_s"] > 0


@pytest.mark.parametrize("name", ["slab.solve", "hoststream.solve"])
def test_the_control_reads_above_the_program(name):
    # one solve each (a window of 0 s runs one), so both read the same seed
    prog = _run(_cell(name), seconds=0)["check"]
    ctl = _run(_cell(name), solver="control", seconds=0)["check"]
    # the control's products run at three bf16 passes; it has to read
    # well above the program in one number (on the CPU its QR is exact)
    assert ctl["resid_left"]["value"] >= 3 * prog["resid_left"]["value"]


def test_a_lower_precision_sweep_fails_the_comparison():
    res = _run(_cell("slab.solve", sweep_dtype="bfloat16"))
    assert not res["correct"]


# -- faults planted under the timed path -------------------------------------

def _faults():
    import repro.core.oom as oom
    import repro.core.operator as op

    def unchanged(self, Q):
        self._count(self.chain_passes)
        return Q

    def dense_half(self, Q):
        self._count(self.chain_passes)
        h = self._X.shape[0] // 2
        return 2 * op._dense_chain(self._X[:h], Q,
                                   sweep_dtype=self.sweep_dtype)

    def host_half(self, Q):
        acc = 0
        for b in range(self.n_blocks // 2):
            acc = acc + 2 * oom.hostblock_chain_step_fn("float32")(
                0 * Q, self.block(b), Q)
        return acc

    def altered(extract):
        def fn(self, Q):
            U, S, V = extract(self, Q)
            return U, S.at[0].multiply(1.01), V
        return fn

    def tail(extract):
        # ascending order: the solve keeps the trailing k of the l
        def fn(self, Q):
            U, S, V = extract(self, Q)
            return U[:, ::-1], S[::-1], V[:, ::-1]
        return fn

    return {
        ("slab.solve", "unchanged"): (op.DenseOperator, "gram_chain",
                                      unchanged),
        ("slab.solve", "half"): (op.DenseOperator, "gram_chain",
                                 dense_half),
        ("slab.solve", "altered"): (op.DenseOperator, "extract",
                                    altered(op.DenseOperator.extract)),
        ("slab.solve", "tail"): (op.DenseOperator, "extract",
                                 tail(op.DenseOperator.extract)),
        ("hoststream.solve", "unchanged"): (oom.HostBlockedMatrix,
                                            "gram_chain", lambda s, Q: Q),
        ("hoststream.solve", "half"): (oom.HostBlockedMatrix, "gram_chain",
                                       host_half),
        ("hoststream.solve", "altered"): (
            op.HostBlockedOperator, "extract",
            altered(op.HostBlockedOperator.extract)),
        ("hoststream.solve", "tail"): (
            op.HostBlockedOperator, "extract",
            tail(op.HostBlockedOperator.extract)),
    }


@pytest.mark.parametrize("name, fault", [
    (c, f) for c in ("slab.solve", "hoststream.solve")
    for f in ("unchanged", "half", "altered", "tail")])
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    cls, attr, fn = _faults()[(name, fault)]
    monkeypatch.setattr(cls, attr, fn)
    res = _run(_cell(name))
    assert not res["correct"], res["check"]


def _patch_svd(monkeypatch, after=lambda res: res, **force):
    """``repro.core.svd`` as the harness calls it, with ``force``
    overriding its arguments and ``after`` applied to its result."""
    import repro.core
    real = repro.core.svd
    monkeypatch.setattr(repro.core, "svd", lambda *a, **kw: after(
        real(*a, **{**kw, **force})))


def test_triplets_out_of_order_fail_only_sigma(monkeypatch):
    _patch_svd(monkeypatch, lambda r: r._replace(
        U=r.U[:, ::-1], S=r.S[::-1], V=r.V[:, ::-1]))
    res = _run(_cell("slab.solve"))
    assert not res["correct"]
    assert [n for n, v in res["check"].items()
            if v["value"] > v["limit"]] == ["sigma_err"]


@pytest.mark.parametrize("fault, force, after", [
    ("stopped_early", {"max_iters": 2}, lambda r: r),
    ("demoted", {}, lambda r: r._replace(backend="memmap")),
    ("faulted", {}, lambda r: r._replace(
        faults={"counters": {"h2d": 1}, "events": []})),
])
def test_a_failed_solve_is_not_correct(monkeypatch, fault, force, after):
    _patch_svd(monkeypatch, after, **force)
    res = _run(_cell("slab.solve"))
    assert res["failed"] == res["attempted"] >= 1, fault
    assert not res["correct"] and res["check"]["failed_solves"]["value"]
    # every solve counts in the timed metric, the failed ones too
    assert res["metrics"]["solve_s"]["value"] > 0


# -- the sharded layout, on four fake CPU devices ---------------------------

_CHILD = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import functools
import jax
from jax.sharding import PartitionSpec as P
import repro.core.operator as op
from bench import harness

def run(solver="program"):
    cell = harness.load_cell("slab.solve")
    with open(sys.argv[1] + "/bench/configs/fig3a-node-4chip.json") as f:
        cell["config"] = dict(json.load(f), **json.loads(sys.argv[2]))
    r = harness.run_cell(cell, int(sys.argv[3]), 0, False,
                         jax.devices()[:4], time.perf_counter(),
                         solver=solver)
    return {"correct": r["correct"], "failed": r["failed"],
            "check": {k: v["value"] for k, v in r["check"].items()}}

out = {"program": run(), "control": run("control")}

@functools.lru_cache(maxsize=None)
def no_psum(mesh, axes, sweep_dtype):
    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(axes[0], None), P()), out_specs=P(),
                       check_vma=False)
    def chain(A, Q):
        return A.T @ (A @ Q)
    return jax.jit(chain)

good = op.sharded_gram_chain_fn
op.sharded_gram_chain_fn = no_psum
out["no_psum"] = run()
op.sharded_gram_chain_fn = good

chain = op.ShardedOperator.gram_chain
op.ShardedOperator.gram_chain = lambda self, Q: (self._count(2), Q)[1]
out["unchanged"] = run()
op.ShardedOperator.gram_chain = chain

extract = op.ShardedOperator.extract
def altered(self, Q):
    U, S, V = extract(self, Q)
    return U, S.at[0].multiply(1.01), V
op.ShardedOperator.extract = altered
out["altered"] = run()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, ROOT,
         json.dumps(TINY["fig3a-node-4chip"]),
         str(SEED)], env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_program_passes_and_control_reads_above(sharded):
    prog, ctl = sharded["program"], sharded["control"]
    assert prog["correct"] and prog["failed"] == 0
    assert ctl["check"]["resid_left"] >= 3 * prog["check"]["resid_left"]


@pytest.mark.parametrize("fault", ["no_psum", "unchanged", "altered"])
def test_a_broken_sharded_path_is_not_correct(sharded, fault):
    assert not sharded[fault]["correct"], sharded[fault]
