"""CPU checks of the per-layer metrics that read the program's own host
spans (``repro.core.spans``) against the device trace: ``queue_wait_ms``,
``stage_h2d_share`` and ``stage_wait_share``, on constructed traces.
"""
import os
import sys

import pytest
from jax.profiler import ProfileData

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def _plane(pid, name, lines):
    """A plane in text proto; ``lines`` maps a line's name to its
    ``(event name, start ms, duration ms)`` events."""
    names = sorted({ev[0] for evs in lines.values() for ev in evs})
    meta = {n: i for i, n in enumerate(names, 1)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (lname, events) in enumerate(lines.items(), 1):
        out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0')
        for ename, start_ms, dur_ms in events:
            out.append(f"events {{ metadata_id: {meta[ename]} "
                       f"offset_ps: {int(start_ms * MS * 1000)} "
                       f"duration_ps: {int(dur_ms * MS * 1000)} }}")
        out.append("}")
    for n, i in meta.items():
        out.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


def _view(host, devices):
    """A run over ``host`` events and one device plane per entry of
    ``devices`` (``{"XLA Modules": [...], "XLA Ops": [...]}``)."""
    planes = [_plane(10 + d, f"/device:TPU:{d}", lines)
              for d, lines in enumerate(devices)]
    planes.append(_plane(1, "/host:CPU", {"python": host}))
    tr = trace_reduce.reduce(ProfileData.from_text_proto("\n".join(planes)))
    lo, hi = trace_reduce.window(tr)
    cfg = harness.load_cell("slab.solve")["config"]
    return harness.RunView(cfg, [], tr, lo, hi, "TPU v5 lite")


def _read(name, view):
    return harness.load_metric(name).read(view)


# -- queue_wait_ms ------------------------------------------------------------

#: two solves; the host hands each sweep over in 1 ms, the chip starts
#: them 9, 17 and 25 ms after that in the first solve, 4 ms in the second
SOLVES = [("bench.solve", 0, 100), ("bench.solve", 100, 100)]
CHAINS = [("op.chain", 10, 1), ("op.chain", 12, 1), ("op.chain", 14, 1),
          ("op.chain", 110, 1)]
SWEEPS = [("jit__dense_chain(3)", 20, 10), ("jit__dense_chain(3)", 30, 10),
          ("jit__dense_chain(3)", 40, 10), ("jit__orth(4)", 50, 2),
          ("jit__dense_chain(3)", 115, 10)]


def _chip(modules):
    return {"XLA Modules": modules,
            "XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", s, d)
                        for _, s, d in modules]}


def test_queue_wait_is_the_median_device_start_after_the_span():
    view = _view(SOLVES + CHAINS, [_chip(SWEEPS)] * 2)
    # waits 9, 17, 25 and 4 ms on each chip
    assert _read("queue_wait_ms", view) == pytest.approx(13.0)


@pytest.mark.parametrize("host, modules", [
    # a sweep missing on the chip in the second solve
    (SOLVES + CHAINS, SWEEPS[:-1]),
    # a span missing: a tier whose op.chain covers many programs
    (SOLVES + CHAINS[:-1], SWEEPS),
])
def test_queue_wait_reads_nothing_when_counts_differ(host, modules):
    assert _read("queue_wait_ms", _view(host, [_chip(modules)])) is None


# -- stage_h2d_share, stage_wait_share ---------------------------------------

#: one solve, 0..100 ms; chip 0 runs ops 10..40 and 50..60 ms, chip 1
#: runs through the whole window
STAGED = [("bench.solve", 0, 100),
          ("stage.h2d", 0, 20), ("stage.h2d", 45, 10),
          ("stage.pace", 60, 20), ("stage.pace", 70, 5)]
BUSY = [{"XLA Modules": [("jit__step(5)", 10, 30), ("jit__step(5)", 50, 10)],
         "XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 10, 30),
                     ("%fusion.1 = f32[8] fusion(%p)", 50, 10)]},
        {"XLA Modules": [("jit__step(5)", 0, 100)],
         "XLA Ops": [("%fusion.1 = f32[8] fusion(%p)", 0, 100)]}]


@pytest.mark.parametrize("name, chip0", [
    # 0..20 and 45..55 ms staged, 10..20 and 50..55 busy: 15 ms idle
    ("stage_h2d_share", 15.0),
    # 60..80 ms waiting (one span inside another counted once), all idle
    ("stage_wait_share", 20.0),
])
def test_staging_share_counts_only_idle_time_under_its_span(name, chip0):
    assert _read(name, _view(STAGED, BUSY[:1])) == pytest.approx(chip0)
    # averaged over the chips: the second is never idle
    assert _read(name, _view(STAGED, BUSY)) == pytest.approx(chip0 / 2)


# -- without the spans (a program that has none) or a device plane ----------

@pytest.mark.parametrize("name", ["queue_wait_ms", "stage_h2d_share",
                                  "stage_wait_share"])
@pytest.mark.parametrize("host, devices", [
    (SOLVES, [_chip(SWEEPS)]),                  # no spans of the program
    (SOLVES + CHAINS + STAGED[1:], []),          # no device plane
])
def test_nothing_to_read_gives_nothing(name, host, devices):
    assert _read(name, _view(host, devices)) is None
