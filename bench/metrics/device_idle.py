"""Device: the share of the traced window in which no operation ran,
``1 - (union of the device's op intervals) / window``, averaged over
the chips.  Reads the device trace.
"""
from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.devices or run.hi <= run.lo:
        return None
    idle = [1.0 - trace_reduce.busy_ns(d, run.lo, run.hi) / (run.hi - run.lo)
            for d in run.trace.devices if d.ops]
    return 100.0 * sum(idle) / len(idle) if idle else None
