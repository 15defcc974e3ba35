"""Jaxpr contract pass: collectives, precision, purity — no solve needed.

The solver's performance story is a set of *schedule* invariants:

* **collective contract** — one sharded block step issues exactly ONE
  fused ``psum`` whose payload is the ``(n, k)`` iterate (``(k, k)`` for
  the Rayleigh–Ritz Gram, ``(n,)``/``(k,)`` for the paper-faithful
  deflation schedule); no stray ``all_gather``/``all_reduce`` sneaks in;
* **precision contract** — every ``dot_general`` whose operands are
  bf16 accumulates fp32 (``preferred_element_type=float32`` shows up in
  the jaxpr as a float32 output aval on narrow operands), and nothing
  in a step silently upcasts to f64;
* **purity contract** — a traced step contains no host callbacks
  (``io_callback``/``pure_callback``/``debug_callback``): host syncs
  live OUTSIDE the step, behind the sanctioned lagged-sync helper.

All three are decidable from ``jax.make_jaxpr`` of the *driver's own*
jitted step functions (``core/operator.py`` builders — the same
callables ``core/svd.py`` dispatches), so the checks run in milliseconds
with ``ShapeDtypeStruct`` inputs and can't drift from the solver.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.analysis.report import Violation

__all__ = ["StepContract", "trace_jaxpr", "iter_eqns", "check_step",
           "COLLECTIVE_PRIMS"]

#: primitive names (normalized: "-" -> "_") that move data across shards
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "all_reduce",
    "collective_permute",
})

#: substrings identifying host round-trip primitives (purity contract)
_CALLBACK_MARKERS = ("callback", "infeed", "outfeed")


@dataclasses.dataclass(frozen=True)
class StepContract:
    """What one traced step function is allowed to do.

    ``psum_payloads`` is the exact multiset of per-psum payload shapes
    (each entry a tuple-of-shapes, one per psum operand); its length IS
    the required psum count.  ``requires_bf16`` asserts the narrow
    sweep actually happened (a bf16 config whose trace shows zero bf16
    dots silently fell back to fp32 — that's drift, not a win).
    """

    psum_payloads: tuple = ()        # e.g. (((160, 8),),) — one (n,k) psum
    allowed_collectives: frozenset = frozenset()   # besides psum
    requires_bf16: bool = False
    forbid_f64: bool = True


def trace_jaxpr(fn, *args):
    """Closed jaxpr of ``fn`` on abstract inputs — traces, never runs."""
    return jax.make_jaxpr(fn)(*args)


def _sub_jaxprs(eqn):
    """Nested jaxprs of one equation (pjit/shard_map/scan/pallas_call...)."""
    subs = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                subs.append(x.jaxpr)      # ClosedJaxpr
            elif hasattr(x, "eqns"):
                subs.append(x)            # raw Jaxpr
    return subs


def iter_eqns(jaxpr):
    """All equations of a (closed) jaxpr, depth-first through sub-jaxprs."""
    if hasattr(jaxpr, "jaxpr"):          # ClosedJaxpr -> Jaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


#: primitives reported under another name: inside ``shard_map`` jax
#: binds ``psum`` of a varying value as ``psum_invariant``.  ``pvary``
#: (a type cast with no data movement) is never a collective.
_PRIM_ALIASES = {"psum_invariant": "psum"}


def _prim(eqn) -> str:
    name = eqn.primitive.name.replace("-", "_")
    return _PRIM_ALIASES.get(name, name)


def _np_dtype(aval):
    """numpy dtype of an aval, or None for extended dtypes (PRNG keys).

    ``np.dtype(key<fry>)`` does NOT raise — it silently coerces to
    float64 — so extended dtypes must be screened out explicitly.
    """
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return None
    try:
        if jax.dtypes.issubdtype(dt, jax.dtypes.extended):
            return None
        return np.dtype(dt)
    except TypeError:
        return None


def _avals_in(eqn):
    return [v.aval for v in eqn.invars if hasattr(v, "aval")]


def _avals_out(eqn):
    return [v.aval for v in eqn.outvars if hasattr(v, "aval")]


def _shape_sig(avals) -> tuple:
    return tuple(tuple(int(d) for d in a.shape) for a in avals)


def collective_schedule(jaxpr) -> list:
    """Ordered collective ops in the trace: (prim, shapes, dtypes, bytes)."""
    sched = []
    for eqn in iter_eqns(jaxpr):
        p = _prim(eqn)
        if p in COLLECTIVE_PRIMS:
            avals = [a for a in _avals_in(eqn)
                     if _np_dtype(a) is not None]
            sched.append({
                "prim": p,
                "shapes": [list(s) for s in _shape_sig(avals)],
                "dtypes": [_np_dtype(a).name for a in avals],
                "bytes": int(sum(int(np.prod(a.shape, dtype=np.int64)) *
                                 _np_dtype(a).itemsize for a in avals)),
            })
    return sched


def check_step(jaxpr, contract: StepContract, tag: str,
               pass_name: str = "jaxpr"):
    """Check one traced step against its contract.

    Returns ``(violations, details)``: the violations list (empty when
    clean) and the measured facts (collective schedule, dot census) for
    the report.
    """
    violations = []
    psums = []
    n_dots = n_bf16_dots = 0

    for eqn in iter_eqns(jaxpr):
        p = _prim(eqn)
        avals_in = _avals_in(eqn)

        if p == "psum":
            psums.append(_shape_sig(avals_in))
        elif p in COLLECTIVE_PRIMS and p not in contract.allowed_collectives:
            violations.append(Violation(
                pass_name, "stray-collective", tag,
                f"collective {p!r} on shapes {_shape_sig(avals_in)} is not "
                f"in the step's contract (allowed: psum"
                + (f" + {sorted(contract.allowed_collectives)}"
                   if contract.allowed_collectives else "") + ")"))

        if p == "dot_general":
            n_dots += 1
            narrow = any(str(a.dtype) in ("bfloat16", "float16")
                         for a in avals_in)
            if narrow:
                n_bf16_dots += 1
                out = _avals_out(eqn)
                # NB: guard None — np.dtype(...) == None is TRUE in
                # numpy (None coerces to the default dtype, float64)
                if any(d is not None and d != np.dtype("float32")
                       for d in map(_np_dtype, out)):
                    violations.append(Violation(
                        pass_name, "bf16-accum", tag,
                        f"dot_general with bf16 operands produces "
                        f"{[np.dtype(a.dtype).name for a in out]} output — "
                        f"missing preferred_element_type=float32 (silent "
                        f"narrow accumulation)"))

        if contract.forbid_f64:
            for a in avals_in + _avals_out(eqn):
                d = _np_dtype(a)
                if d is not None and d == np.dtype("float64"):
                    violations.append(Violation(
                        pass_name, "f64-upcast", tag,
                        f"primitive {p!r} touches a float64 aval of shape "
                        f"{tuple(a.shape)} — silent f64 upcast in a step "
                        f"that contracts fp32/bf16"))
                    break

        if any(m in p for m in _CALLBACK_MARKERS):
            violations.append(Violation(
                pass_name, "host-callback", tag,
                f"primitive {p!r} is a host round-trip inside a traced "
                f"step — host syncs belong outside the step, behind the "
                f"sanctioned lagged-sync helper"))

    expected = sorted(contract.psum_payloads)
    actual = sorted(psums)
    if len(psums) != len(contract.psum_payloads):
        violations.append(Violation(
            pass_name, "collective-count", tag,
            f"expected exactly {len(contract.psum_payloads)} psum(s) per "
            f"step, traced {len(psums)} (payloads: {actual})"))
    elif expected != actual:
        violations.append(Violation(
            pass_name, "collective-payload", tag,
            f"psum payload shapes {actual} != contract {expected}"))

    if contract.requires_bf16 and n_bf16_dots == 0:
        violations.append(Violation(
            pass_name, "bf16-not-applied", tag,
            "config says sweep_dtype=bfloat16 but the trace has no bf16 "
            "dot_general — the narrow sweep silently fell back to fp32"))

    details = {
        "n_psum": len(psums),
        "psum_payloads": [[list(s) for s in sig] for sig in psums],
        "n_dot_general": n_dots,
        "n_bf16_dots": n_bf16_dots,
        "collectives": collective_schedule(jaxpr),
    }
    return violations, details
