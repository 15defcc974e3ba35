"""Benchmark orchestrator: one module per paper table/figure.

  accuracy           — t-SVD vs LAPACK (validation table)
  scaling_dense      — paper Fig 3a (dense strong/weak scaling)
  scaling_sparse     — paper Fig 3b (sparse Alg-4 scaling, 128 PB setup)
  oom_batching       — paper Fig 4  (peak memory & time vs n_b, q_s)
  block_vs_deflation — passes-over-A + wall-clock: block subspace
                       iteration vs rank-one deflation
  warmstart          — range-finder warm start: iterations-to-convergence
                       cold vs warmup_q=1, all four paths
  update             — svd_update() warm restarts: O(1) iterations on
                       perturbed matrices vs a cold re-solve
  precision          — mixed-precision (bf16) block sweeps: accuracy +
                       sweep time/bytes fp32 vs bf16, all four paths
  disk_tier          — svd() on a memmap file larger than the host
                       budget (disk->host->device byte accounting)
  serving            — SVD-as-a-service: micro-batched burst throughput
                       vs sequential svd(), streaming under mixed load
  roofline           — §Roofline terms from the dry-run artifacts

``python -m benchmarks.run [--full]``
"""
from __future__ import annotations

import argparse
import json
import os
import time
import traceback


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger problem sizes (slower)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (accuracy, block_vs_deflation, disk_tier,
                            oom_batching, precision, roofline,
                            scaling_dense, scaling_sparse, serving,
                            update, warmstart)
    suite = {
        "accuracy": accuracy.run,
        "scaling_dense": scaling_dense.run,
        "scaling_sparse": scaling_sparse.run,
        "oom_batching": oom_batching.run,
        "block_vs_deflation": block_vs_deflation.run,
        "warmstart": warmstart.run,
        "update": update.run,
        "precision": precision.run,
        "disk_tier": disk_tier.run,
        "serving": serving.run,
        "roofline": roofline.run,
    }
    results = {}
    for name, fn in suite.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"\n######## {name} ########")
        try:
            results[name] = {"ok": True, "wall_s": None}
            fn(fast=not args.full)
            results[name]["wall_s"] = round(time.time() - t0, 1)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            results[name] = {"ok": False, "error": str(e)}
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "results")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench_summary.json"), "w") as f:
        json.dump(results, f, indent=2)
    print("\n== summary ==")
    for k, v in results.items():
        print(f"  {k}: {'ok' if v.get('ok') else 'FAIL'} "
              f"({v.get('wall_s', '?')}s)")
    if not all(v.get("ok") for v in results.values()):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
