"""CPU checks of the benchmark's yardstick: the trace reduction, the
roofline arithmetic, the peaks table, the lookup of every part by name,
the shape of ``BENCHMARK.json``, and the refusal to run without a TPU.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest
from jax.profiler import ProfileData

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def _plane(pid, name, lines, names):
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, events) in enumerate(lines.items(), 1):
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for meta, start_ms, dur_ms in events:
            out.append(f"events {{ metadata_id: {meta} "
                       f"offset_ps: {start_ms * MS * 1000} "
                       f"duration_ps: {dur_ms * MS * 1000} }}")
        out.append("}")
    for meta, ename in names.items():
        out.append(f'event_metadata {{ key: {meta} value {{ id: {meta} '
                   f'name: "{ename}" }} }}')
    out.append("}")
    return "\n".join(out)


def _trace(devices=2, allreduce=True):
    """Two chips run the same schedule in a 0..100 ms window:

    * ``jit__dense_chain`` 10..40 ms, its ops ``fusion.1`` 10..30 and
      ``all-reduce.1`` 25..40 (overlapping the fusion by 5 ms);
    * ``jit__orth`` 50..60 ms with op ``custom-call.2`` 50..60;
    * the host: ``bench.solve`` 5..95 ms, inside it ``pjit_orth``
      42..62 ms.
    """
    # ``XLA Ops`` events carry the whole HLO instruction; a fusion that
    # reads an all-reduce's output names it too
    names = {1: "jit__dense_chain(7)", 2: "jit__orth(3)",
             3: "%fusion.1 = f32[32,8]{1,0} fusion(f32[64,32]{1,0} %x)",
             4: ("%all-reduce.1 = f32[32,8]{1,0} all-reduce(%fusion.1)"
                 if allreduce else "%fusion.9 = f32[8] fusion(%all-reduce.0)"),
             5: "%custom-call.2 = f32[32,8]{1,0} custom-call(%p)"}
    planes = [_plane(10 + d, f"/device:TPU:{d}", {
        "XLA Modules": [(1, 10, 30), (2, 50, 10)],
        "XLA Ops": [(3, 10, 20), (4, 25, 15), (5, 50, 10)],
    }, names) for d in range(devices)]
    planes.append(_plane(1, "/host:CPU", {
        "main": [(6, 5, 90), (7, 42, 20)]},
        {6: "bench.solve", 7: "pjit_orth"}))
    return trace_reduce.reduce(ProfileData.from_text_proto("\n".join(planes)))


def _view(tr, config=None, kind="TPU v5 lite"):
    lo, hi = trace_reduce.window(tr)
    return harness.RunView(config or _config("fig3a-slab-1chip"), [], tr,
                           lo, hi, kind)


def _config(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the trace reduction ------------------------------------------------------

def test_reduction_gives_busy_idle_and_per_program_time():
    tr = _trace()
    assert [d.name for d in tr.devices] == ["/device:TPU:0", "/device:TPU:1"]
    lo, hi = trace_reduce.window(tr)
    assert (lo, hi) == (5 * MS, 95 * MS)
    dev = tr.devices[0]
    # ops cover 10..40 and 50..60 ms
    assert trace_reduce.busy_ns(dev, lo, hi) == 40 * MS
    assert trace_reduce.program_ns(dev, ("_dense_chain",), lo, hi) \
        == (30 * MS, 1)
    assert trace_reduce.program_ns(dev, ("_orth", "_gap"), lo, hi) \
        == (10 * MS, 1)
    assert trace_reduce.program("jit__dense_extract(12)") == \
        "_dense_extract"


def test_interval_arithmetic():
    ev = [trace_reduce.Event("a", 0, 10), trace_reduce.Event("b", 5, 20),
          trace_reduce.Event("c", 30, 40)]
    merged = trace_reduce.merge(ev, 2, 35)
    assert merged == [[2, 20], [30, 35]]
    assert trace_reduce.length(merged) == 23
    assert trace_reduce.subtract([[0, 100]], merged) == \
        [[0, 2], [20, 30], [35, 100]]
    assert trace_reduce.subtract([[0, 10]], [[0, 10]]) == []


def test_breakdown_names_programs_and_host_work():
    tr = _trace()
    lo, hi = trace_reduce.window(tr)
    progs = dict(trace_reduce.top_programs(tr, lo, hi))
    assert progs == pytest.approx({"_dense_chain": 0.030, "_orth": 0.010})
    assert [e.name for e in tr.devices[0].ops] == \
        ["fusion.1", "all-reduce.1", "custom-call.2"]
    gaps = dict(trace_reduce.idle_gaps(tr, lo, hi))
    # idle: 5..10, 40..50 and 60..95 ms; the middles 7.5 and 77.5 lie
    # under bench.solve alone, 45 under pjit_orth
    assert gaps == pytest.approx({"bench.solve": 0.040, "pjit_orth": 0.010})


def test_device_metrics_on_a_constructed_trace():
    view = _view(_trace())
    idle = harness.load_metric("device_idle").read(view)
    assert idle == pytest.approx(100 * (1 - 40 / 90))
    small = harness.load_metric("small_ops_share").read(view)
    assert small == pytest.approx(100 * 10 / 40)
    psum = harness.load_metric("psum_exposed_share").read(view)
    # the all-reduce runs 25..40 ms; 25..30 overlaps fusion.1
    assert psum == pytest.approx(100 * 10 / 90)


def test_missing_device_ops_give_nothing_not_zero():
    bare = _view(trace_reduce.Trace([], [trace_reduce.Event(
        "bench.solve", 0, 10 * MS)]))
    no_ar = _view(_trace(allreduce=False))
    for name in ("sweep_roofline", "small_ops_share", "device_idle",
                 "psum_exposed_share"):
        assert harness.load_metric(name).read(bare) is None, name
    assert harness.load_metric("psum_exposed_share").read(no_ar) is None


# -- the roofline arithmetic ----------------------------------------------

@pytest.mark.parametrize("name, rows", [
    ("fig3a-slab-1chip", 65536),
    ("fig3a-hoststream-1chip", 163840 // 32),
    ("fig3a-node-4chip", 262144 // 4),
])
def test_sweep_roofline_counts_one_read_of_a_per_chain(name, rows):
    mod = harness.load_metric("sweep_roofline")
    cfg = _config(name)
    peaks = harness.peaks_for("TPU v5 lite")
    assert mod.rows_per_call(cfg) == rows
    n, k = 32768, 32
    read_s = (rows * n * 4 + 2 * n * k * 4) / 819e9
    mxu_s = 4 * rows * n * k / (197e12 / 6)
    assert read_s > mxu_s                       # memory-bound at k = 32
    assert mod.least_seconds(cfg, peaks) == pytest.approx(read_s)
    if name == "fig3a-slab-1chip":
        # one 8.59 GB read at 819 GB/s: 10.5 ms
        assert read_s == pytest.approx(10.49e-3, rel=1e-3)


def test_sweep_roofline_share_on_a_trace():
    view = _view(_trace())
    share = harness.load_metric("sweep_roofline").read(view)
    least = harness.load_metric("sweep_roofline").least_seconds(
        view.config, view.peaks())
    # one chain call of 30 ms on each chip
    assert share == pytest.approx(100 * least / 30e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        harness.peaks_for("TPU v9 imaginary")
    view = _view(_trace(), kind="cpu")
    with pytest.raises(KeyError):
        harness.load_metric("sweep_roofline").read(view)


# -- the parts, found by name ---------------------------------------------

def test_every_cell_loads_with_its_parts():
    bench = _bench()
    from bench import check
    for wl in bench["workloads"]:
        cell = harness.load_cell(wl["name"])
        assert set(cell["limits"]) == set(check.NAMES)
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert callable(harness.load_metric(m["name"]).read)
        assert cell["config"]["chips"] == wl["chips"]


def test_a_new_metric_and_cell_need_only_new_files(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench")
    bench = _bench()
    bench["workloads"].append(dict(bench["workloads"][0],
                                   name="slab.dummy", traffic="dummy-mix"))
    bench["per_layer"].append({
        "name": "dummy_count", "unit": "solves", "better": "higher",
        "source": "program_counter", "layer": "solver driver",
        "moves": "solve_s", "workloads": ["slab.dummy"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics" / "dummy_count.py").write_text(
        "def read(run):\n    return float(len(run.solves))\n")
    traffic = json.loads((root / "bench" / "traffic" /
                          "solve-planted.json").read_text())
    traffic["check_sample"] = 3
    (root / "bench" / "traffic" / "dummy-mix.json").write_text(
        json.dumps(traffic))
    shutil.copy(root / "bench" / "limits" / "slab.solve.json",
                root / "bench" / "limits" / "slab.dummy.json")

    cell = harness.load_cell("slab.dummy", root=str(root))
    assert cell["traffic"]["check_sample"] == 3
    assert [m["name"] for m in cell["per_layer"]][-1] == "dummy_count"
    view = harness.RunView(cell["config"], [harness.Solve(0, 1.0)] * 2,
                           None, 0, 1, "cpu", str(root))
    assert harness.load_metric("dummy_count", str(root)).read(view) == 2.0
    assert "slab.dummy" not in {w["name"] for w in _bench()["workloads"]}


def test_a_dotted_metric_reads_as_its_quantity():
    # a quantity split off for cells with another end-to-end metric: the
    # same reader and the same arithmetic, under a bound of its own
    assert harness.reader_path("device_idle.stream") == \
        harness.reader_path("device_idle")
    solves = [harness.Solve(0, 2.0), harness.Solve(1, 4.0)]
    assert harness.end_to_end("solve_s.stream", solves, 9.0, 6.5) == \
        harness.end_to_end("solve_s", solves, 9.0, 6.5) == 3.25
    with pytest.raises(KeyError):
        harness.end_to_end("rate_s.stream", solves, 9.0, 6.5)


# -- BENCHMARK.json ---------------------------------------------------------

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_keeps_its_contract():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for c in configs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        body = _config(c["name"])
        for key in c["reduced"]:
            assert key in body and not key.endswith(("_dim", "_rank"))
    for w in cells.values():
        assert _NAME.match(w["name"]) and w["config"] in configs
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert sum(w["chips"] == 4 for w in cells.values()) <= \
        max(1, len(cells) // 2)
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        assert os.path.isfile(harness.reader_path(m["name"]))
        # every cell it lists reports the end-to-end metric it moves
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w]), (m["name"], w)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert _NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])


# -- no chip, no run ---------------------------------------------------------

def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_the_harness_exits_nonzero_without_a_tpu():
    out = _run(ROOT, "--workload", "slab.solve", "--seed", "3000000001",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert out.stdout.strip() == "" and "no CPU mode" in out.stderr


def test_the_harness_exits_nonzero_with_only_its_own_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    out = _run(tmp_path, "--workload", "slab.solve", "--seed", "1",
               "--seconds", "1")
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("layout", ["dense", "host"])
def test_planted_matrix_has_its_spectrum(layout):
    from bench.datagen import Planted
    p = Planted(512, 192, 8, seed=3, noise_rel=1e-3)
    A = np.asarray(getattr(p, layout)(), np.float64)
    sv = np.linalg.svd(A, compute_uv=False)
    # Weyl: the noise moves every sigma by at most its bound
    assert np.all(np.abs(sv[:16] - p.s) <= 1e-5 * p.s[0] + p.bound)
    assert sv[16] <= p.bound
    assert np.array_equal(A, np.asarray(getattr(p, layout)(), np.float64))


def test_planted_seed_keys_do_not_collide_past_32_bits():
    from bench.datagen import seed_key
    import jax
    a = jax.random.key_data(seed_key(5))
    b = jax.random.key_data(seed_key(5 + (1 << 32)))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# -- the window ----------------------------------------------------------------

def test_the_window_runs_whole_solves_and_counts_all_its_time():
    import time
    import types
    calls = []

    def solve(seed, iters):
        calls.append(seed)
        time.sleep(0.02)
        return types.SimpleNamespace(
            U=np.zeros(2), S=np.ones(2), V=np.zeros(2), backend="dense",
            iters=np.full(2, iters), passes_over_A=2 * iters + 1,
            bytes_moved={}, faults=None)

    solves, t0, t1, sample = harness.run_window(
        solve, 0.1, 7, {"backend": "dense", "iters": 3}, sample_size=2)
    assert calls == [7 + s.index for s in solves] and len(calls) >= 4
    assert all(s.ok for s in solves) and len(sample) == 2
    # no solve starts after the window's seconds; the last one ends it
    assert t1 - t0 < 0.1 + 0.02 + 0.05
    # solve_s is the whole window over its solves: at least the solves'
    # own time, with the host's time between them
    assert harness.end_to_end("solve_s", solves, 1.0, t1 - t0) * len(solves) \
        >= sum(s.wall_s for s in solves)
