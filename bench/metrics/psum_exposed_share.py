"""Collectives: the all-reduce time during which no other operation runs
on the same chip, as a share of the traced window, averaged over the
chips.  All-reduces are the device ops named ``all-reduce*`` (the
``shard_map`` psum, and its ``-start``/``-done`` halves where XLA splits
it).  Reads the device trace.
"""
from bench import trace_reduce


def read(run):
    if run.trace is None or run.hi <= run.lo:
        return None
    shares = []
    for dev in run.trace.devices:
        ar = [e for e in dev.ops if e.name.startswith("all-reduce")]
        if not ar:
            continue
        rest = [e for e in dev.ops if not e.name.startswith("all-reduce")]
        exposed = trace_reduce.subtract(
            trace_reduce.merge(ar, run.lo, run.hi),
            trace_reduce.merge(rest, run.lo, run.hi))
        shares.append(100.0 * trace_reduce.length(exposed)
                      / (run.hi - run.lo))
    return sum(shares) / len(shares) if shares else None
