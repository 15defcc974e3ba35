"""Mixed-precision sweep policy for the block subspace iterate.

The block method's hot loop is two A-sized sweeps per step (``A Q`` then
``A^T Y``), and every backend is data-movement bound on exactly those
sweeps.  Running them in bf16 halves the bytes of the *A-operand* term —
on-device HBM reads, and H2D block copies on the OOM path — while the
MXU still accumulates in fp32 (``preferred_element_type=float32``),
mirroring the reduced-precision matmul strategy of GPU-centred SVD work
(Liu et al., arXiv:2508.11467) and the out-of-core block RSVD pipeline
of Lu et al. (arXiv:1706.07191).  Collective (psum) payloads are fp32
accumulator outputs and are deliberately NOT narrowed — distributed
sweep bytes halve per chip, collective bytes stay unchanged.

The policy is deliberately narrow — ONE knob, threaded everywhere:

* ``sweep_dtype`` ∈ {``"float32"``, ``"bfloat16"``} — the dtype the
  A-sized *operands* are cast to for the two sweeps (and for the
  warm-start sketch/refinement sweeps, which are the same operator).
* accumulation is pinned to fp32: every ``dot`` specifies
  ``preferred_element_type=float32``, so partial sums never round to
  bf16.
* QR, Rayleigh–Ritz, eigh, psum payloads, and every factor (``U, S, V``,
  the iterate ``Q``) stay fp32 — only the sweep *inputs* are low
  precision, so the iterate's orthonormality and the extraction are
  full-precision.

``sweep_dtype="float32"`` is the default and is bit-stable with the
pre-policy code path (the cast is a no-op and the contraction is the
same fp32 dot).  bf16 sweeps converge to ~1e-2..1e-3 relative
reconstruction error (bf16 has an 8-bit mantissa: inputs round at
~4e-3 relative); pair them with a correspondingly looser ``eps``
(~1e-4) — the subspace-convergence test cannot resolve angles below the
bf16 noise floor, so a tighter ``eps`` just burns ``max_iters``.

Pass accounting (``LinearOperator.passes`` in ``core/operator.py``; the
per-method formulas are documented in ``core/tsvd.py``) is
dtype-independent: a pass is one A-sized operand sweep no matter how
wide the elements are — bf16 changes the *bytes per pass* (2 instead of
4 per element), never the number of passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SWEEP_DTYPES = ("float32", "bfloat16")


def fp32_dots():
    """Context in which every fp32 ``dot`` is computed in fp32.

    At its DEFAULT precision XLA:TPU feeds float32 dot operands through
    ONE bfloat16 MXU pass, so an "fp32" sweep, the subspace gap and the
    Rayleigh–Ritz extraction would carry bf16 rounding (~4e-3) and the
    gap test could never reach ``eps``.  The solver entry points trace
    under this context; bf16 sweeps are unaffected (their operands
    already are bf16), and on the CPU nothing changes.
    """
    return jax.default_matmul_precision("highest")


def resolve_sweep_dtype(sweep_dtype) -> jnp.dtype:
    """Validate + canonicalize the policy knob to a jnp dtype.

    Accepts the policy strings (preferred — they are hashable and jit-
    static) or the equivalent jnp/np dtypes.
    """
    try:
        name = jnp.dtype(sweep_dtype).name
    except TypeError as e:
        raise ValueError(f"unsupported sweep_dtype {sweep_dtype!r}; "
                         f"expected one of {SWEEP_DTYPES}") from e
    if name not in SWEEP_DTYPES:
        raise ValueError(
            f"unsupported sweep_dtype {sweep_dtype!r}; expected one of "
            f"{SWEEP_DTYPES} (accumulation is always float32)")
    return jnp.dtype(name)
