"""The host spans the solver marks in a profiler trace.

Each span is a ``jax.profiler.TraceAnnotation``: it lands in the
profiler's own trace, on the same clock as the device planes, and costs
well under a microsecond when no profiler runs.  Spans carry a name and
no arguments; on one thread, nesting gives each span its parent.
``SPANS`` is the one list of them; every site opens its span through
``span(name)``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SPANS", "span"]

#: span name -> what it covers
SPANS = {
    "svd.solve": "one svd() call at the front door, to its factors ready",
    "svd.iter": "one iteration's dispatch in the driver loop (step())",
    "op.chain": "handing one gram_chain sweep to the runtime: one program "
                "on the dense and sharded tiers, a whole streamed pass on "
                "the host tier",
    "svd.sync": "the one sanctioned device->host read (host_sync_scalar)",
    "svd.extract": "the Rayleigh-Ritz extraction pass (finalize)",
    "stage.h2d": "the host side of one host block's H2D copy",
    "stage.pace": "waiting for the step that consumed block b-1 before "
                  "block b+1's copy is issued",
}


def span(name: str) -> TraceAnnotation:
    """The profiler span ``name``, one of ``SPANS``."""
    return TraceAnnotation(name)
