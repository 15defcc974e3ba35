"""Memory/traffic pass: peak live bytes + A-traffic, statically.

Two estimates, both read off the traced jaxpr (no solve, no device):

* ``peak_live_bytes`` — a liveness scan over the step's equations:
  a value is live from the equation that defines it to its last use,
  inputs are live from entry, outputs to the end.  Sub-jaxprs
  (pjit/shard_map/scan bodies) contribute their own peak *minus* their
  boundary values (already counted in the outer frame).  The estimate
  is checked against a per-device budget — the "does the step fit"
  proof the mesh-scale-up work needs before touching real hardware.

* A-traffic — the bytes the step's ``dot_general``s and Pallas kernels
  actually read of the A-sized operand (``dot_read_bytes``), or the
  bytes of the staged block argument for the host-streamed step
  functions.  Summed over a
  backend's step traces this must equal the solver's OWN accounting
  (``chain_passes * op.bytes_per_pass``), so the static estimate and
  the runtime ``passes``/``bytes_moved`` counters can't diverge: change
  one without the other and this pass fails.

Collective payload bytes come from the same walk (psum operand avals),
giving the cross-check that a bf16 sweep config moves HALF the HBM
bytes but IDENTICAL collective bytes (the psum payload stays the fp32
accumulator).
"""
from __future__ import annotations

import numpy as np

from repro.analysis.jaxpr_check import (COLLECTIVE_PRIMS, _np_dtype, _prim,
                                        _sub_jaxprs, iter_eqns)
from repro.analysis.report import Violation

__all__ = ["aval_bytes", "peak_live_bytes", "collective_payload_bytes",
           "dot_read_bytes", "check_memory"]


def aval_bytes(aval) -> int:
    dt = _np_dtype(aval)
    # extended dtype (PRNG key): one fry key = two uint32 words
    itemsize = 8 if dt is None else dt.itemsize
    return int(np.prod(aval.shape, dtype=np.int64)) * itemsize


def _is_var(v) -> bool:
    # Literals carry .val and are unhashable; they're inline constants,
    # not buffers, so the liveness scan skips them.
    return hasattr(v, "aval") and not hasattr(v, "val")


def _var_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    return aval_bytes(aval)


def peak_live_bytes(jaxpr) -> int:
    """Liveness-scan peak over one jaxpr frame, recursing into bodies."""
    if hasattr(jaxpr, "jaxpr"):                       # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    eqns = jaxpr.eqns
    n = len(eqns)

    last_use: dict = {}
    roots = list(jaxpr.invars) + list(jaxpr.constvars)
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if _is_var(v):
                last_use[v] = i
    for v in jaxpr.outvars:
        if _is_var(v):
            last_use[v] = n

    live = {v for v in roots if _is_var(v)}
    peak = sum(_var_bytes(v) for v in live)
    cur = peak
    for i, eqn in enumerate(eqns):
        # outputs materialize while inputs are still held (conservative)
        for v in eqn.outvars:
            if _is_var(v) and v not in live:
                live.add(v)
                cur += _var_bytes(v)
        inner = 0
        for sub in _sub_jaxprs(eqn):
            body = sub.jaxpr if hasattr(sub, "jaxpr") else sub
            io = sum(_var_bytes(v) for v in
                     list(body.invars) + list(body.constvars)
                     + list(body.outvars))
            inner = max(inner, max(0, peak_live_bytes(sub) - io))
        peak = max(peak, cur + inner)
        dead = [v for v in live if last_use.get(v, -1) <= i]
        for v in dead:
            live.discard(v)
            cur -= _var_bytes(v)
    return peak


def collective_payload_bytes(jaxpr) -> int:
    """Total bytes of all collective operands in the trace (per step)."""
    total = 0
    for eqn in iter_eqns(jaxpr):
        if _prim(eqn) in COLLECTIVE_PRIMS:
            total += sum(_var_bytes(v) for v in eqn.invars)
    return total


def dot_read_bytes(jaxpr, a_nbytes: int) -> int:
    """Bytes of A-sized ``dot_general`` and ``pallas_call`` operands
    read by the trace.

    An operand counts as "A-sized" when its aval is exactly
    ``a_nbytes`` — the shard/block of A at the sweep dtype.  Transposes
    and dtype casts of A keep the byte size, so the measure is stable
    under the sweeps' layout changes; iterate-sized (n, k) operands
    never match.  A Pallas kernel's grid reads each block of its A
    operand once, so the operand counts once, however many dots its
    body runs on the blocks (those are block-sized, never A-sized).
    """
    total = 0
    for eqn in iter_eqns(jaxpr):
        if _prim(eqn) in ("dot_general", "pallas_call"):
            for v in eqn.invars:
                if _var_bytes(v) == a_nbytes:
                    total += a_nbytes
    return total


def check_memory(jaxpr, tag: str, *, budget_bytes: int | None = None,
                 a_nbytes: int | None = None, mode: str = "dots"):
    """Peak + traffic measurements for one trace, with the budget check.

    Returns ``(violations, details)``.  ``mode="dots"`` measures
    A-traffic as A-sized dot operands; ``mode="staged"`` as the staged
    block argument itself (the host-streamed step functions read the
    block once for both fused halves).
    """
    violations = []
    peak = peak_live_bytes(jaxpr)
    coll = collective_payload_bytes(jaxpr)
    a_bytes = None
    if a_nbytes is not None:
        a_bytes = (a_nbytes if mode == "staged"
                   else dot_read_bytes(jaxpr, a_nbytes))
    if budget_bytes is not None and peak > budget_bytes:
        violations.append(Violation(
            "memory", "budget", tag,
            f"estimated peak live bytes {peak:,} exceed the device "
            f"budget {budget_bytes:,}"))
    details = {"peak_live_bytes": int(peak),
               "collective_bytes": int(coll)}
    if a_bytes is not None:
        details["a_read_bytes"] = int(a_bytes)
    return violations, details
