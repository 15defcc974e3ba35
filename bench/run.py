#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU and print its result line.

    python3 bench/run.py --workload slab.solve --seed 7 --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and metrics are found
by name from ``BENCHMARK.json`` (see ``bench/harness.py``).  The program
under test is ``repro.core.svd`` from this checkout's ``src/``.  With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result's JSON object;
the last lines of standard error are the compared numbers, each with its
limit.  There is no CPU mode: without a TPU, or with fewer chips than
the cell asks for, the run exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"bench: no program under test at {SRC}/repro",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, SRC]
    from bench import harness

    cell = harness.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s), "
              f"JAX found {len(devices)} {devices[0].platform} device(s); "
              f"there is no CPU mode", file=sys.stderr)
        return 2
    import repro
    where = [os.path.abspath(p) for p in repro.__path__]
    if where != [os.path.join(SRC, "repro")]:
        print(f"bench: imported repro from {where}, not {SRC}",
              file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program, however quick to compile, is kept: set-up of a
    # second run then loads them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"jax {jax.__version__}, {devices[0].device_kind} x "
                f"{len(devices)}, compile cache {cache}")

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices[:cell["chips"]],
                              T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
