"""One run of one benchmark cell, driven by the files the cell names.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics; the harness finds every part by that name:

* ``bench/configs/<config>.json``   sizes, dtypes, memory tier, backend;
* ``bench/traffic/<traffic>.json``  parameters of the one generator here
  (``run_window``): a closed loop of back-to-back solves, each running
  the configuration's fixed number of iterations (``iters``);
* ``bench/limits/<cell>.json``      the limit of each number that
  ``bench/check.py`` compares;
* ``bench/metrics/<metric>.py``     one reader per per-layer metric, with
  ``read(run) -> float | None``;
* ``bench/peaks.json``              the chip's peaks by ``device_kind``.

A run: make the matrix from the seed (span ``bench.datagen``), warm up
every program the window will run (span ``bench.warmup``), then solve
back to back for ``seconds`` (one ``bench.solve`` span per solve; no
solve starts after ``seconds``, the last one is waited for), read the
device's peak memory, and compare a sample of the solves with the
reference.  ``setup_s`` is process start to the first timed solve.  A
traced run profiles a window of at most ``TRACE_SECONDS``.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib.util
import json
import os
import random
import shutil
import sys
import time

import jax
import numpy as np

from bench import check, datagen, reference, trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where a traced run writes the profiler's trace (inside the checkout)
TRACE_DIR = ".bench_trace"
#: a traced run's window is cut to this many seconds: the per-layer
#: metrics are steady well within it, and a trace of four chips over a
#: whole window takes minutes to write and read
TRACE_SECONDS = 20


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding the parts by name
# ---------------------------------------------------------------------------

def _json(path: str):
    with open(path) as f:
        return json.load(f)


def _entry(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _for_cell(metrics, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell names, read from its files."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    wl = _entry(bench["workloads"], name, "workload")
    cfg = _entry(bench["configs"], wl["config"], "config")
    config = _json(os.path.join(root, cfg["file"]))
    if config.get("chips", 1) != wl["chips"]:
        raise ValueError(f"{name}: config {cfg['name']} is laid out on "
                         f"{config.get('chips', 1)} chips, the cell asks "
                         f"for {wl['chips']}")
    return {
        "name": name, "chips": wl["chips"], "config": config,
        "traffic": _json(os.path.join(root, "bench", "traffic",
                                      wl["traffic"] + ".json")),
        "limits": _json(os.path.join(root, "bench", "limits",
                                     name + ".json")),
        "end_to_end": _for_cell(bench["end_to_end"], name),
        "per_layer": _for_cell(bench["per_layer"], name),
    }


def reader_path(name: str, root: str = ROOT) -> str:
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``.
    A dotted name ``<quantity>.<suffix>`` is one quantity split off for
    cells that report another end-to-end metric; without a reader of its
    own it is read by ``<quantity>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    if "." in name and not os.path.isfile(path):
        path = os.path.join(root, "bench", "metrics",
                            name.split(".")[0] + ".py")
    return path


def load_metric(name: str, root: str = ROOT):
    """The reader module of per-layer metric ``name`` (``reader_path``)."""
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), reader_path(name, root))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str, root: str = ROOT) -> dict:
    table = _json(os.path.join(root, "bench", "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# Data, the program's solver and the reference's
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Data:
    A: object               # jax.Array (one device or sharded) or ndarray
    ops: reference.RefOps
    s: np.ndarray           # the planted leading k singular values, float64
    mesh: object = None


def make_data(config: dict, traffic: dict, seed: int, devices) -> Data:
    """The cell's matrix, made from ``seed`` in its memory tier."""
    m, n, k = config["m"], config["n"], config["k"]
    planted = datagen.Planted(m, n, k, seed, **traffic["planted"])
    tier, s = config["tier"], planted.s[:k]
    if tier == "device":
        A = planted.dense()
        return Data(A, reference.RefOps(A), s)
    if tier == "host":
        A = planted.host()
        return Data(A, reference.RefOps(A, n_blocks=config["n_blocks"]), s)
    if tier == "sharded":
        mesh = jax.sharding.Mesh(np.asarray(devices[:config["chips"]]),
                                 ("data",))
        A = planted.sharded(mesh)
        return Data(A, reference.RefOps(A, mesh=mesh), s, mesh)
    raise ValueError(f"unknown memory tier {tier!r}")


def program_solver(config: dict, data: Data):
    """``solve(seed, iters)`` through ``repro.core.svd`` in its fixed-
    iteration mode (``force_iters``), with the input the configuration's
    tier gives it and no demotion."""
    from repro.core import svd
    kw = dict(sweep_dtype=config["sweep_dtype"], force_iters=True,
              demote_on_oom=False)
    if config["tier"] == "host":
        kw["n_blocks"] = config["n_blocks"]
    if config["tier"] == "sharded":
        kw["mesh"] = data.mesh

    def solve(seed: int, iters: int):
        return svd(data.A, config["k"], seed=seed, max_iters=iters, **kw)

    return solve


def reference_solver(config: dict, data: Data, precision: str):
    """``solve(seed, iters)`` through the plain reference at
    ``precision``; at ``"high"`` this is the control."""

    def solve(seed: int, iters: int):
        return reference.tsvd(data.ops, config["k"], seed, iters,
                              precision=precision,
                              backend=config["backend"])

    return solve


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

def warm_up(solve, seed: int) -> None:
    """One solve cut to two iterations: the first iteration takes the
    start block, the second an iterate the first produced (placed as
    every later one is), and the extraction runs once; so every program
    of a full solve is compiled or loaded, at three passes over ``A``."""
    res = solve(seed, 2)
    jax.block_until_ready((res.U, res.S, res.V))


@dataclasses.dataclass
class Solve:
    index: int
    wall_s: float
    error: str | None = None       # why the solve failed, if it did
    iters: int = 0
    passes: int = 0
    host_bytes: int = 0

    @property
    def ok(self) -> bool:
        return self.error is None


def _failure(res, backend: str, iters: int) -> str | None:
    """Why a returned solve broke a guarantee of its configuration: it
    ran on another backend (a demotion), ran another number of
    iterations than the configuration fixes, or recorded faults."""
    if res.backend != backend:
        return f"ran on backend {res.backend!r}, wanted {backend!r}"
    if int(np.max(res.iters)) != iters:
        return (f"ran {int(np.max(res.iters))} iterations, the "
                f"configuration fixes {iters}")
    if (res.faults or {}).get("counters"):
        return f"fault telemetry recorded {res.faults['counters']}"
    return None


class _CompileCounter:
    """Counts programs compiled or loaded while ``on``."""

    def __init__(self):
        self.on, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def run_window(solve, seconds: float, seed: int, config: dict,
               sample_size: int, compiles: _CompileCounter | None = None):
    """Back-to-back solves of ``config["iters"]`` iterations each for
    ``seconds``; each solve's seed is ``seed`` plus its index.  No solve
    starts after ``seconds``; the last one is waited for.  Returns the
    solves, the first start and the last completion on the host clock,
    and a sample of the returned ``(U, S, V)`` drawn from ``seed``
    (reservoir sampling)."""
    backend, iters = config["backend"], config["iters"]
    rng = random.Random(seed)
    solves, sample = [], []
    t_first = time.perf_counter()
    if compiles is not None:
        compiles.on = True
    i = 0
    while i == 0 or time.perf_counter() - t_first < seconds:
        with jax.profiler.TraceAnnotation("bench.solve"):
            t0 = time.perf_counter()
            try:
                res = solve(seed + i, iters)
                jax.block_until_ready((res.U, res.S, res.V))
            except Exception as e:  # noqa: BLE001 - a failed solve is counted
                solves.append(Solve(i, time.perf_counter() - t0,
                                    f"{type(e).__name__}: {e}"))
                i += 1
                continue
            wall = time.perf_counter() - t0
        solves.append(Solve(i, wall, _failure(res, backend, iters),
                            iters=int(np.max(res.iters)),
                            passes=int(res.passes_over_A),
                            host_bytes=int((res.bytes_moved or {})
                                           .get("host", 0))))
        item = (res.U, res.S, res.V)
        if len(sample) < sample_size:
            sample.append(item)
        else:
            j = rng.randrange(i + 1)
            if j < sample_size:
                sample[j] = item
        i += 1
    t_end = time.perf_counter()
    if compiles is not None:
        compiles.on = False
    return solves, t_first, t_end, sample


def peak_bytes(devices) -> list:
    """``peak_bytes_in_use`` of each device (None where the backend
    keeps no memory statistics)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def memory_peak_bytes(devices) -> int | None:
    """The peak of the fullest device."""
    peaks = peak_bytes(devices)
    return None if None in peaks else int(max(peaks))


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunView:
    """What a per-layer reader sees of one traced run."""
    config: dict
    solves: list
    trace: trace_reduce.Trace | None
    lo: float                 # traced window on the trace's clock (ns)
    hi: float
    device_kind: str
    root: str = ROOT

    def peaks(self) -> dict:
        """The chip's peaks; a ``device_kind`` not in the table raises."""
        return peaks_for(self.device_kind, self.root)


def end_to_end(name: str, solves, setup_s: float, window_s: float):
    """An end-to-end metric over every solve of the window, failed ones
    included (a run with a failed solve is not correct).  ``solve_s`` is
    the window's seconds, from the first start to the last completion,
    host time between solves included, over its solves.  A dotted name ``<metric>.<suffix>`` is the same
    quantity with a bound of its own, for the cells it lists."""
    base = name.split(".")[0]
    if base == "setup_s":
        return setup_s
    if base == "solve_s":
        return window_s / len(solves)
    raise KeyError(f"no end-to-end metric named {name!r}")


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float, solver: str = "program",
             root: str = ROOT) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``devices`` are the chips the cell uses.  ``solver`` is
    ``"program"``, or ``"control"`` for the plain reference at
    ``Precision.HIGH`` in the program's place.
    """
    config, traffic = cell["config"], cell["traffic"]
    if (traffic["loop"], traffic["callers"]) != ("closed", 1):
        raise ValueError(f"{cell['name']}: the generator runs a closed "
                         f"loop with one caller, the mix asks for "
                         f"{traffic['loop']} with {traffic['callers']}")
    compiles = _CompileCounter()
    with jax.profiler.TraceAnnotation("bench.datagen"):
        t0 = time.perf_counter()
        data = make_data(config, traffic, seed, devices)
        jax.block_until_ready(data.A)
        log(f"datagen {time.perf_counter() - t0:.3f} s, peak device bytes "
            f"{memory_peak_bytes(devices)}")
    if solver == "program":
        solve = program_solver(config, data)
    elif solver == "control":
        solve = reference_solver(config, data, "high")
    else:
        raise ValueError(f"solver must be 'program' or 'control', got "
                         f"{solver!r}")
    with jax.profiler.TraceAnnotation("bench.warmup"):
        t0 = time.perf_counter()
        warm_up(solve, seed)
        log(f"warmup {time.perf_counter() - t0:.3f} s")

    trace_dir = os.path.join(root, TRACE_DIR)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        solves, t_first, t_end, sample = run_window(
            solve, min(seconds, TRACE_SECONDS) if trace else seconds, seed,
            config, traffic["check_sample"], compiles)
    finally:
        if trace:
            jax.profiler.stop_trace()
    setup_s = t_first - t_start
    peak = memory_peak_bytes(devices)
    failed = [s for s in solves if not s.ok]
    for s in failed[:5]:
        log(f"solve {s.index} failed: {s.error}")
    walls = np.array([s.wall_s for s in solves])
    log(f"solve seconds: min {walls.min():.4f} median "
        f"{np.median(walls):.4f} p95 {np.percentile(walls, 95):.4f} max "
        f"{walls.max():.4f}; iterations "
        f"{dict(sorted(collections.Counter(s.iters for s in solves).items()))}")
    log(f"solve walls {[round(float(w), 4) for w in walls]}")
    log(f"window {t_end - t_first:.3f} s, {len(solves)} solves, "
        f"{len(failed)} failed, {compiles.n} programs compiled or loaded "
        f"in the window, peak_bytes_in_use per chip {peak_bytes(devices)}")

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": len(solves),
              "failed": len(failed), "metrics": {}, "device": device}
    if trace:
        tr = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = trace_reduce.window(tr)
        view = RunView(config, solves, tr, lo, hi, dev.device_kind, root)
        for m in cell["per_layer"]:
            v = load_metric(m["name"], root).read(view)
            if v is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [trace_reduce.busy_ns(d, lo, hi) for d in tr.devices]
        device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = {
            "device_ops": trace_reduce.top_programs(tr, lo, hi),
            "idle_gaps": trace_reduce.idle_gaps(tr, lo, hi)}
    else:
        for m in cell["end_to_end"]:
            v = end_to_end(m["name"], solves, setup_s, t_end - t_first)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}

    numbers = check.compare(data.ops, data.s, sample) if sample else {}
    judged = check.verdict(numbers, cell["limits"])
    result["correct"] = (bool(judged) and not failed
                         and all(j["ok"] for j in judged.values()))
    log(f"check failed_solves {len(failed)} limit 0 "
        f"{'FAILED' if failed else 'ok'}")
    for name, j in judged.items():
        log(f"check {name} {j['value']!r} limit {j['limit']!r} "
            f"{'ok' if j['ok'] else 'FAILED'}")
    result["check"] = {"failed_solves": {"value": len(failed), "limit": 0},
                       **{name: {"value": j["value"], "limit": j["limit"]}
                          for name, j in judged.items()}}
    return result
