"""Operator small ops: the device time of the QR (``_orth``), the
subspace gap (``_gap``) and the Rayleigh-Ritz extraction
(``_dense_extract``, sharded ``extract``), as a share of the device's
busy time, per chip, averaged over the chips.  Reads the device trace.
"""
from bench import trace_reduce

PROGRAMS = ("_orth", "_gap", "_dense_extract", "extract")


def read(run):
    if run.trace is None:
        return None
    shares = []
    for dev in run.trace.devices:
        ns, calls = trace_reduce.program_ns(dev, PROGRAMS, run.lo, run.hi)
        busy = trace_reduce.busy_ns(dev, run.lo, run.hi)
        if calls and busy:
            shares.append(100.0 * ns / busy)
    return sum(shares) / len(shares) if shares else None
