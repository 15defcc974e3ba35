"""Chaos drill: a solve survives injected faults AND a real process kill.

    PYTHONPATH=src python examples/chaos_demo.py

Three legs, all verified against an uninterrupted fault-free reference:

1. **transient I/O** — a disk-tier solve with injected read failures
   retries under bounded backoff and finishes bitwise-identical, with
   the injected faults and retries reported in ``SVDResult.faults``;
2. **numeric corruption + tier demotion** — a NaN planted in a sweep is
   caught by the health guard and rolled back; an injected device OOM
   demotes the solve down the memory ladder mid-run, carrying the warm
   iterate;
3. **kill -9 under fault injection** — a CHILD PROCESS runs a
   checkpointed solve with a fault plan that both flakes the disk reads
   and calls ``os._exit`` after iteration 2; the parent observes the
   real death, then resumes from the checkpoint directory (with ANOTHER
   transient fault injected for good measure) to bitwise-identical
   sigmas and conserved pass accounting.

The child/parent split uses the ``REPRO_CHAOS_ROLE`` env var; CI runs
this file as its kill-under-injected-fault two-process smoke.  The
child runs FIRST, before the parent touches JAX: on an accelerator a
parent that has initialized JAX holds the device, and the child could
not get it.
"""
import os
import subprocess
import sys
import tempfile

import numpy as np

from repro.core import (FaultPlan, FaultSpec, inject_faults, stage_to_disk,
                        svd)

M, N, K = 384, 128, 8
SEED = 7
EXIT_CODE = 42


def make_matrix():
    rng = np.random.default_rng(0)
    U, _, Vt = np.linalg.svd(rng.normal(size=(M, N)).astype(np.float32),
                             full_matrices=False)
    S = np.concatenate([np.linspace(25, 4, K),
                        2 * 0.8 ** np.arange(1, N - K + 1)])
    return (U * S) @ Vt


def solve(path, ckpt=None):
    return svd(path, K, method="block", seed=SEED, n_blocks=4,
               io_retry_backoff=0.0, checkpoint_dir=ckpt,
               checkpoint_every=1)


def child(path, ckpt):
    """Run a checkpointed solve that flakes a disk read AND dies for
    real after iteration 2 — the parent asserts on the exit code."""
    plan = FaultPlan(FaultSpec(site="disk_read", at=2, count=1),
                     FaultSpec(site="kill", at=2, mode="exit",
                               exit_code=EXIT_CODE))
    with inject_faults(plan):
        solve(path, ckpt=ckpt)
    print("child: survived the kill?!", file=sys.stderr)
    sys.exit(1)


def main():
    A = make_matrix()
    workdir = tempfile.mkdtemp(prefix="chaos_demo_")
    path = stage_to_disk(A, os.path.join(workdir, "a.npy"))

    # -- leg 3, first half: the child dies mid-solve (no JAX here yet) --
    ckpt = os.path.join(workdir, "ckpt")
    env = dict(os.environ, REPRO_CHAOS_ROLE="child")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), path, ckpt], env=env)
    assert proc.returncode == EXIT_CODE, \
        f"child exited {proc.returncode}, wanted {EXIT_CODE}"
    steps = [n for n in os.listdir(ckpt) if n.startswith("step_")]
    print(f"kill: child died with os._exit({EXIT_CODE}), "
          f"checkpoints survived: {sorted(steps)}")

    ref = solve(path)
    print(f"reference: converged={ref.converged} "
          f"passes={ref.passes_over_A} backend={ref.backend}")

    # -- leg 1: transient disk faults, retried to a bitwise result ------
    with inject_faults(FaultPlan(FaultSpec(site="disk_read", at=3,
                                           count=2))):
        res = solve(path)
    assert np.array_equal(np.asarray(ref.S), np.asarray(res.S))
    print(f"transient-I/O: bitwise OK, faults={res.faults['counters']}")

    # -- leg 2a: NaN sweep -> health-guard rollback ---------------------
    with inject_faults(FaultPlan(FaultSpec(site="sweep", at=2))):
        res = solve(path)
    assert np.array_equal(np.asarray(ref.S), np.asarray(res.S))
    assert res.passes_over_A == ref.passes_over_A
    print(f"NaN-sweep: rolled back bitwise, "
          f"faults={res.faults['counters']}")

    # -- leg 2b: device OOM -> tier demotion dense -> hostblocked -------
    import jax.numpy as jnp
    dref = svd(jnp.asarray(A), K, method="block", seed=SEED)
    with inject_faults(FaultPlan(FaultSpec(site="device_oom", at=2))):
        res = svd(jnp.asarray(A), K, method="block", seed=SEED)
    assert res.backend == "hostblocked"
    np.testing.assert_allclose(np.asarray(res.S), np.asarray(dref.S),
                               rtol=1e-4)
    print(f"device-OOM: demoted dense->{res.backend}, sigmas agree, "
          f"faults={res.faults['counters']}")

    # -- leg 3, second half: resume the killed solve ---------------------
    with inject_faults(FaultPlan(FaultSpec(site="disk_read", at=1))):
        res = solve(path, ckpt=ckpt)
    assert np.array_equal(np.asarray(ref.S), np.asarray(res.S))
    assert res.passes_over_A == ref.passes_over_A
    print(f"resume: bitwise OK across the kill, passes conserved "
          f"({res.passes_over_A}), faults={res.faults['counters']}")
    print("chaos demo: all legs OK")


if __name__ == "__main__":
    if os.environ.get("REPRO_CHAOS_ROLE") == "child":
        child(sys.argv[1], sys.argv[2])
    else:
        main()
