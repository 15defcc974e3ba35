"""Solver driver: how long a dispatched sweep waits in the runtime's
queue before the chip starts it, in ms.

Reads the program's host spans and the device trace.  Within each
``bench.solve`` span, the i-th ``op.chain`` span (the host handing one
sweep to the runtime) is paired with the i-th chain program of
``sweep_roofline.PROGRAMS`` on the same chip, in start order; the wait
is the program's device start minus the span's end.  A solve's device
work ends before its ``bench.solve`` span closes, so the pairing is
exact where the counts agree; where they differ in any solve (a tier
whose ``op.chain`` covers many programs) there is nothing to read.  The
median over the window; the minimum goes to the log (near 0: a host
stall drained the queue).
"""
import statistics

from bench import harness, trace_reduce


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    chains = run.trace.spans("op.chain")
    if not chains:
        return None
    programs = harness.load_metric("sweep_roofline", run.root).PROGRAMS
    waits = []
    for solve in run.trace.spans("bench.solve"):
        host = [c for c in chains
                if solve.start <= c.start and c.end <= solve.end]
        for dev in run.trace.devices:
            dispatched = [e for e in dev.modules
                          if trace_reduce.program(e.name) in programs
                          and solve.start <= e.start and e.end <= solve.end]
            if len(dispatched) != len(host):
                return None
            waits += [p.start - h.end for h, p in zip(host, dispatched)]
    if not waits:
        return None
    harness.log(f"queue_wait_ms: {len(waits)} sweeps, min "
                f"{min(waits) / 1e6:.3f} ms, max {max(waits) / 1e6:.3f} ms")
    return statistics.median(waits) / 1e6
