"""Reduce a JAX profiler trace to what the per-layer metrics read.

``load(log_dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
under ``log_dir`` with ``jax.profiler.ProfileData`` and keeps, per
device plane (``/device:TPU:<i>``), the events of its ``XLA Ops`` line
(one per operation the device ran) and of its ``XLA Modules`` line (one
per program, named ``jit_<function>(<id>)``), and the events of every
host thread (the benchmark's own ``bench.*`` spans among them).  Device
and host events share one clock, in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

from jax.profiler import ProfileData

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    devices: list
    host: list

    def spans(self, name: str) -> list:
        return [e for e in self.host if e.name == name]


def program(module_name: str) -> str:
    """``jit__dense_chain(12)`` -> ``_dense_chain``: the jitted function
    a program was built from."""
    base = module_name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def op_name(text: str) -> str:
    """``%fusion.1 = f32[...] fusion(...)`` -> ``fusion.1``: an ``XLA
    Ops`` event is named by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda s: s) -> list:
    return sorted((Event(name(e.name), e.start_ns, e.end_ns)
                   for e in line.events), key=lambda e: e.start)


def reduce(data: ProfileData) -> Trace:
    devices, host = [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(DeviceTrace(
                plane.name,
                _events(lines["XLA Ops"], op_name) if "XLA Ops" in lines
                else [],
                _events(lines["XLA Modules"]) if "XLA Modules" in lines
                else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(_events(ln))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(devices, sorted(host, key=lambda e: e.start))


def load(log_dir: str) -> Trace:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return reduce(ProfileData.from_file(paths[0]))


def merge(events, lo: float, hi: float) -> list:
    """The union of the events' intervals, clipped to ``[lo, hi]``, as
    sorted disjoint ``[start, end]`` pairs."""
    out = []
    for s, e in sorted((max(ev.start, lo), min(ev.end, hi))
                       for ev in events):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """``a`` minus ``b``, both sorted disjoint interval lists."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def busy_ns(dev: DeviceTrace, lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi]`` in which some operation ran on
    ``dev``."""
    return length(merge(dev.ops, lo, hi))


def program_ns(dev: DeviceTrace, names, lo: float, hi: float) -> tuple:
    """``(device ns, calls)`` of the programs built from the jitted
    functions ``names`` within ``[lo, hi]``."""
    evs = [e for e in dev.modules
           if program(e.name) in names and lo <= e.start and e.end <= hi]
    return length(merge(evs, lo, hi)), len(evs)


def window(trace: Trace, span: str = "bench.solve") -> tuple:
    """``[start, end]`` of the traced window: from the first ``span`` to
    the end of the last."""
    spans = trace.spans(span)
    if not spans:
        raise ValueError(f"no {span!r} span in the trace")
    return spans[0].start, max(e.end for e in spans)


def top_programs(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` programs with the most device time in ``[lo, hi]``, as
    ``[jitted function, seconds]``, averaged over the devices."""
    total: dict = {}
    for dev in trace.devices:
        for e in dev.modules:
            if e.end > lo and e.start < hi:
                key = program(e.name)
                total[key] = total.get(key, 0.0) + (min(e.end, hi)
                                                     - max(e.start, lo))
    d = max(len(trace.devices), 1)
    return [[k, v / d / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """Idle time of the first device in ``[lo, hi]``, summed by what the
    host was doing: the innermost host event under each gap's middle
    (``"none"`` where no host event covers it).  ``[[what, seconds]]``,
    largest first."""
    if not trace.devices:
        return []
    busy = merge(trace.devices[0].ops, lo, hi)
    total: dict = {}
    # one sweep: ``open_`` holds the host events begun before the gap's
    # middle, by start; those that ended before it are dropped from the
    # top, since later middles lie further on
    open_, i = [], 0
    for s, e in subtract([[lo, hi]], busy):
        mid = (s + e) / 2
        while i < len(trace.host) and trace.host[i].start <= mid:
            open_.append(trace.host[i])
            i += 1
        while open_ and open_[-1].end < mid:
            open_.pop()
        what = open_[-1].name if open_ else "none"
        total[what] = total.get(what, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
