"""Paper Fig 3a analogue: strong/weak scaling of the dense t-SVD (Alg 3).

The paper scales to 128 A100s.  This container has one CPU core, so the
table combines three sources, clearly labeled:

* ``measured``  — wall time of the real distributed code on N *emulated*
  devices (XLA host-device emulation; collectives execute for real but
  share one core, so times are NOT speedups — they validate overheads);
* ``modeled``   — per-node time from the v5e roofline model:
  compute = local gram+power FLOPs / peak, comm = all-reduce bytes / ICI,
  with the paper's setup (k=32, fixed 100 power iterations, per-node
  matrix block 262144 x 32768 in the weak scaling);
* the strong-scaling column divides the global problem by N like Fig 3a.

``python -m benchmarks.scaling_dense`` prints both tables; the multi-
device measured runs happen in a child process (8 emulated devices).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from benchmarks import hw

# Paper benchmark setup (Fig 3): per-node dense block, k=32, 100 iters.
PAPER_M, PAPER_N = 262_144, 32_768
PAPER_K, PAPER_ITERS = 32, 100


def modeled_times(node_counts=(1, 2, 4, 8, 16, 32)):
    """v5e roofline model of the paper's weak/strong scaling experiment."""
    rows = []
    chips_per_node = 4  # paper: 4 GPUs/node; we keep the same grouping
    for nn in node_counts:
        N = nn * chips_per_node
        # --- weak scaling: every node holds a (M, N) block -> global m grows
        m_loc, n = PAPER_M // chips_per_node, PAPER_N
        gram_flops = 2 * m_loc * n * n                       # local A^T A
        power_flops = PAPER_ITERS * PAPER_K * 2 * n * n      # B v, k ranks
        deflate_flops = PAPER_K * 4 * m_loc * n
        t_comp = (gram_flops + power_flops + deflate_flops) / hw.PEAK_FLOPS
        t_mem = ((m_loc * n * 4) * (PAPER_K * 0.05 + 1)
                 + PAPER_ITERS * PAPER_K * n * n * 4) / hw.HBM_BW
        # all-reduce of B (n x n) once per rank + sigma scalars
        ar_bytes = PAPER_K * n * n * 4 * 2 * (N - 1) / N
        t_comm = ar_bytes / hw.ICI_BW
        weak = max(t_comp, t_mem) + t_comm
        # --- strong scaling: global (M, N) fixed, block shrinks with N
        m_s = PAPER_M // N
        f_comp = (2 * m_s * n * n + power_flops / chips_per_node
                  + PAPER_K * 4 * m_s * n) / hw.PEAK_FLOPS
        strong = max(f_comp, t_mem / N) + t_comm
        rows.append({"nodes": nn, "chips": N,
                     "weak_s": weak, "strong_s": strong,
                     "comm_s": t_comm})
    return rows


_CHILD = r"""
import time, numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh as compat_make_mesh
from repro.core import svd
results = {}
rng = np.random.default_rng(0)
m, n, k = 1024, 256, 8
A = rng.normal(size=(m, n)).astype(np.float32)
for N in (1, 2, 4, 8):
    mesh = compat_make_mesh((N,), ("data",))
    # warmup/compile
    r = svd(jnp.asarray(A), k, mesh=mesh, method="gram", force_iters=True,
            max_iters=5)
    jax.block_until_ready(r.S)
    t0 = time.time()
    r = svd(jnp.asarray(A), k, mesh=mesh, method="gram", force_iters=True,
            max_iters=20)
    jax.block_until_ready(r.S)
    results[N] = time.time() - t0
import json; print("RESULT:" + json.dumps(results))
"""


def measured_emulated():
    env = dict(os.environ)
    # fake CPU devices: the child must never reach for the parent's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise RuntimeError(f"child failed: {out.stderr[-2000:]}")


def run(fast: bool = True):
    print("\n== Dense scaling (paper Fig 3a analogue) ==")
    print("-- modeled on v5e (weak: fixed per-node block; strong: fixed global) --")
    print(f"{'nodes':>6} {'chips':>6} {'weak_s':>10} {'strong_s':>10} {'comm_s':>10}")
    rows = modeled_times()
    for r in rows:
        print(f"{r['nodes']:>6} {r['chips']:>6} {r['weak_s']:>10.3f} "
              f"{r['strong_s']:>10.3f} {r['comm_s']:>10.3f}")
    meas = measured_emulated()
    print("-- measured, emulated devices on ONE core (overhead check, not speedup) --")
    print(f"{'devices':>8} {'wall_s':>10}")
    for n, t in sorted(meas.items(), key=lambda kv: int(kv[0])):
        print(f"{n:>8} {t:>10.2f}")
    return {"modeled": rows, "measured_emulated": meas}


if __name__ == "__main__":
    run()
