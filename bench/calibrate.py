#!/usr/bin/env python3
"""Read the compared numbers of the program and of the control, on many
seeds in one process, to set the limits in ``bench/limits/<cell>.json``.

    python3 bench/calibrate.py --workload slab.solve --seconds 4 \\
        --program 11,12,13 --control 11,12,13 [--reference 11] \\
        [--unsorted 11]

For each seed the cell's matrix is made once; then each solver named for
that seed runs a short window at the cell's own size and load, and its
numbers (``bench/check.py``) are printed as one JSON line.  ``program``
is ``repro.core.svd``; ``control`` is the plain reference at
``Precision.HIGH`` in its place; ``reference`` is the plain reference at
``Precision.HIGHEST``; ``unsorted`` is the program with a fault planted
where its answer is produced: each solve's triplets come back in
ascending order.  Needs the cell's chips, like ``bench/run.py``.
"""
import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SOLVERS = ("program", "control", "reference", "unsorted")


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    for name in SOLVERS:
        ap.add_argument(f"--{name}", type=_seeds, default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import check, harness
    from repro.compile_cache import enable_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"calibrate: needs {cell['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = devices[:cell["chips"]]
    config, traffic = cell["config"], cell["traffic"]

    def one_seed(seed, solvers):
        """Every solver on one matrix; what it holds is freed on return,
        before the next seed's matrix is made."""
        t0 = time.perf_counter()
        data = harness.make_data(config, traffic, seed, devices)
        jax.block_until_ready(data.A)
        gen_s = time.perf_counter() - t0
        for solver in solvers:
            if solver in ("program", "unsorted"):
                solve = harness.program_solver(config, data)
            else:
                solve = harness.reference_solver(
                    config, data,
                    "high" if solver == "control" else "highest")
            t0 = time.perf_counter()
            harness.warm_up(solve, seed)
            warm_s = time.perf_counter() - t0
            solves, _, _, sample = harness.run_window(
                solve, args.seconds, seed, config, traffic["check_sample"])
            if solver == "unsorted":
                sample = [(U[:, ::-1], S[::-1], V[:, ::-1])
                          for U, S, V in sample]
            t0 = time.perf_counter()
            numbers = check.compare(data.ops, data.s, sample)
            walls = sorted(s.wall_s for s in solves if s.ok)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "solver": solver,
                "numbers": numbers,
                "limits_ok": {n: j["ok"] for n, j in check.verdict(
                    numbers, cell["limits"]).items()},
                "solves": len(solves),
                "failed": [s.error for s in solves if not s.ok][:3],
                "iters": dict(collections.Counter(s.iters for s in solves)),
                "wall_min": walls[0] if walls else None,
                "wall_median": walls[len(walls) // 2] if walls else None,
                "wall_max": walls[-1] if walls else None,
                "gen_s": gen_s, "warmup_s": warm_s,
                "check_s": time.perf_counter() - t0,
                "memory_peak_bytes": harness.memory_peak_bytes(devices)}),
                flush=True)

    order = []
    for solver in SOLVERS:
        for seed in getattr(args, solver):
            if seed not in order:
                order.append(seed)
    for seed in order:
        one_seed(seed, [s for s in SOLVERS if seed in getattr(args, s)])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
