"""Host staging: the share of the traced window in which the host is
inside a ``stage.h2d`` span (issuing one host block's H2D copy) and the
chip runs no operation, in %, averaged over the chips.  Reads the
program's host spans and the device trace.
"""
from bench import trace_reduce


def idle_under(run, span: str):
    """% of the window under ``span`` with the chip idle, averaged over
    the chips; ``None`` without such spans or device ops."""
    if run.trace is None or run.hi <= run.lo:
        return None
    inside = trace_reduce.merge(run.trace.spans(span), run.lo, run.hi)
    if not inside:
        return None
    shares = [100.0 * trace_reduce.length(trace_reduce.subtract(
                  inside, trace_reduce.merge(d.ops, run.lo, run.hi)))
              / (run.hi - run.lo)
              for d in run.trace.devices if d.ops]
    return sum(shares) / len(shares) if shares else None


def read(run):
    return idle_under(run, "stage.h2d")
