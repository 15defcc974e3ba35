"""Dry-run of the distributed SVD itself on the production mesh.

Lowers ONE deflated power step (the paper's inner loop) for the paper's
1 TB dense problem — global A is (8.4M x 32768) fp32, 4.3 GB/chip on the
16x16 mesh — in four variants:

  gram/faithful    Alg 3, B replicated via all-reduce (paper)
  gram/opt         B row-sharded via reduce-scatter + gather-invariant (ours)
  chain/faithful   Alg 4, three all-reduces per step (paper lines 6/8/16)
  chain/opt        fused single all-reduce per step (ours)

  block/opt        block subspace iteration: one (n, k) psum per step
                   advances ALL k ranks (ours; deflation pays per-rank)
  block/warm       randomized range-finder warm start: the sketch psum
                   ``A^T Omega`` plus one fused refinement — the one-off
                   cost that replaces ~10-15 cold block steps with 1-2
  block/bf16       the block step under sweep_dtype="bfloat16": the
                   4.3 GB/chip shard is read at 2 bytes/element by both
                   sweeps (fp32 MXU accumulation); the (n, k) psum
                   payload and QR stay fp32 — per-chip HBM bytes of the
                   dominant term halve, collective bytes are unchanged

Records FLOPs / bytes / per-collective bytes for §Perf — the
paper-faithful vs beyond-paper comparison on the technique itself.

Every variant also carries its COLLECTIVE CONTRACT (the exact psum
schedule the variant is allowed to lower to, see
``analysis/jaxpr_check.py``); ``main()`` checks each trace against it
and exits nonzero with an expected-vs-actual schedule diff when one
drifts — the dry-run is a failing check, not just a printout.
"""
import os
import sys

from repro.launch.xla_flags import HOST_DEVICES_512, ensure_xla_flag

ensure_xla_flag(HOST_DEVICES_512)  # append, never clobber, before jax

import functools  # noqa: E402
import json       # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.jaxpr_check import (StepContract,  # noqa: E402
                                        check_step, trace_jaxpr)
from repro.core.dist_svd import (_deflated_chain_step,  # noqa: E402
                                 _all_gather_inv)
from repro.core.operator import (sharded_block_step_fn,  # noqa: E402
                                 sharded_gram_chain_fn,
                                 sharded_sketch_fn)
from repro.launch.dryrun import analyze, RESULTS_DIR  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

# Paper's 1 TB dense benchmark: 32 nodes x (262144 x 32768) fp32.
M_GLOBAL = 262_144 * 32
N = 32_768
K = 32


def variant_contract(tag: str, mesh) -> StepContract:
    """The exact psum schedule each lowered variant is allowed to have.

    Per-shard payload shapes, as they appear inside the shard_map body.
    This table IS the documented collective story of the §Perf
    comparison — a variant whose trace drifts from it fails the
    dry-run.
    """
    nd = mesh.shape["data"]
    L = K + 8
    return {
        # Alg 4 paper lines 6/8/16: three all-reduces per deflated step
        "chain/faithful": StepContract(
            psum_payloads=(((N,),), ((K,),), ((N,),))),
        # ours: one fused all-reduce of the concatenated payloads
        "chain/opt": StepContract(psum_payloads=(((N + K,),),)),
        # Alg 3: B = psum(X^T X) replicated on every chip
        "gram/faithful": StepContract(psum_payloads=(((N, N),),)),
        # ours: B row-sharded via reduce-scatter + gather-invariant
        "gram/opt": StepContract(
            psum_payloads=(((N // nd, N),),),
            allowed_collectives=frozenset(
                {"psum_scatter", "reduce_scatter", "all_gather"})),
        # block subspace iteration: ONE (n, k) psum advances all K ranks
        "block/opt": StepContract(psum_payloads=(((N, K),),)),
        # bf16 twin: SAME schedule (fp32 payload), narrow sweeps required
        "block/bf16": StepContract(psum_payloads=(((N, K),),),
                                   requires_bf16=True),
        # range-finder warm start: sketch psum + one fused refinement
        "block/warm": StepContract(psum_payloads=(((N, L),), ((N, L),))),
    }[tag]


def variant_fn_args(mesh, kind: str, faithful: bool):
    """The power-step callable + abstract args for one variant — shared
    by the lowering (``lower_variant``) and the contract trace."""
    axes = ("data", "model")  # flatten the whole pod over both axes
    row_spec = P(axes, None)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(row_spec, row_spec, P(None), P(None, None), P(None)),
        out_specs=P(None))
    def power_step(A_loc, U_loc, S, V, v):
        if kind == "chain":
            v1 = _deflated_chain_step(A_loc, U_loc, S, V, v, axes,
                                      faithful=faithful, n_blocks=1)
        else:
            X_loc = A_loc - (U_loc * S[None, :]) @ V.T
            if faithful:
                B = jax.lax.psum(X_loc.T @ X_loc, axes)
                v1 = B @ v
            else:
                B_loc = jax.lax.psum_scatter(
                    X_loc.T @ X_loc, "data", scatter_dimension=0, tiled=True)
                B_loc = jax.lax.psum(B_loc, ("model",))
                v1 = _all_gather_inv(B_loc @ v, "data", tiled=True)
        return v1 / jnp.sqrt(jnp.sum(v1 * v1))

    sds = lambda shape, spec: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=NamedSharding(mesh, spec))
    args = (
        sds((M_GLOBAL, N), row_spec),
        sds((M_GLOBAL, K), row_spec),
        sds((K,), P(None)),
        sds((N, K), P(None, None)),
        sds((N,), P(None)),
    )
    return power_step, args


def lower_variant(mesh, kind: str, faithful: bool):
    fn, args = variant_fn_args(mesh, kind, faithful)
    return jax.jit(fn).lower(*args)


def lower_block_variant(mesh, sweep_dtype="float32"):
    """One BLOCK subspace step (method="block"): the EXACT jitted
    ``ShardedOperator`` step the state-machine driver runs per
    ``core/svd.py::step`` — ``operator.py::sharded_block_step_fn``, the
    fused ``psum(A_loc^T (A_loc Q))`` (ONE (n, k) collective advances
    all K ranks) composed with the driver's QR re-orthonormalization.
    Lowering the driver's own function means the analyzed schedule can't
    drift from ``repro.core.svd``.  ``sweep_dtype="bfloat16"`` lowers
    the mixed-precision twin: both A-sized sweeps read the 2-byte shard
    copy with fp32 MXU accumulation; the psum payload and the QR stay
    fp32 — per-chip HBM bytes of the dominant term halve, collective
    bytes are identical."""
    fn, args = block_variant_fn_args(mesh, sweep_dtype)
    return fn.lower(*args)


def block_variant_fn_args(mesh, sweep_dtype="float32"):
    axes = ("data", "model")
    row_spec = P(axes, None)
    block_step = sharded_block_step_fn(mesh, axes, sweep_dtype)

    sds = lambda shape, spec: jax.ShapeDtypeStruct(
        shape, jnp.float32, sharding=NamedSharding(mesh, spec))
    args = (sds((M_GLOBAL, N), row_spec), sds((N, K), P(None, None)))
    return block_step, args


def lower_block_warm_variant(mesh):
    """The range-finder warm start (method="block", warmup_q=1): the
    driver's ``ShardedOperator`` sketch step (each shard generates its
    own Gaussian Omega row block — the (m, l) Omega is never resident —
    and ONE psum reduces ``A^T Omega``) + QR + one fused ``(n, l)``
    refinement + QR.  A one-off cost of the same shape as ~2.5 block
    steps that buys ~10x fewer iterations on separated spectra (see
    benchmarks/warmstart.py)."""
    fn, args = block_warm_variant_fn_args(mesh)
    return jax.jit(fn).lower(*args)


def block_warm_variant_fn_args(mesh):
    axes = ("data", "model")
    row_spec = P(axes, None)
    L = K + 8                                          # oversampled width
    sketch = sharded_sketch_fn(mesh, axes, L, "float32")
    chain = sharded_gram_chain_fn(mesh, axes, "float32")

    def warm_step(A, seed_arr):
        Y = jnp.linalg.qr(sketch(A, seed_arr))[0]      # sketch: ONE psum
        return jnp.linalg.qr(chain(A, Y))[0]           # q=1 refinement

    sds = lambda shape, dtype, spec: jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec))
    args = (sds((M_GLOBAL, N), jnp.float32, row_spec),
            sds((1,), jnp.uint32, P(None)))
    return warm_step, args


def check_variant_contract(tag, fn, args, mesh) -> list:
    """Trace one variant and diff its psum schedule against the table.

    Returns the violations (empty when the schedule matches); prints the
    expected-vs-actual diff when it doesn't.
    """
    contract = variant_contract(tag, mesh)
    violations, details = check_step(
        trace_jaxpr(fn, *args), contract, tag, pass_name="dryrun")
    if violations:
        print(f"[FAIL] {tag}: collective contract violated", flush=True)
        print(f"       expected psums: "
              f"{[list(map(list, s)) for s in contract.psum_payloads]}")
        print(f"       traced   psums: {details['psum_payloads']}")
        for v in violations:
            print(f"       - {v.rule}: {v.message}")
    return violations


def main():
    mesh = make_production_mesh()
    out = {}
    bad = []
    for kind in ("chain", "gram"):
        for faithful in (True, False):
            tag = f"{kind}/{'faithful' if faithful else 'opt'}"
            print(f"[run ] svd power step {tag}", flush=True)
            fn, args = variant_fn_args(mesh, kind, faithful)
            bad += check_variant_contract(tag, fn, args, mesh)
            out[tag] = analyze(jax.jit(fn).lower(*args))
            r = out[tag]
            print(f"[ ok ] {tag}: flops={r.get('flops', 0):.3e} "
                  f"coll={r.get('collective_bytes_total', 0)/1e6:.1f}MB",
                  flush=True)
    # the block method's step (all K ranks per pass; divide its
    # per-step cost by K when comparing against the per-rank variants),
    # its bf16-sweep twin (same collectives, half the per-chip HBM
    # bytes on the dominant A term), and the range-finder warm start
    # (one-off; replaces ~10x the steps) — all lowered from the SAME
    # jitted ShardedOperator step functions the svd() driver runs
    for tag, fa in (
            ("block/opt", lambda: block_variant_fn_args(mesh)),
            ("block/bf16",
             lambda: block_variant_fn_args(mesh, "bfloat16")),
            ("block/warm", lambda: block_warm_variant_fn_args(mesh))):
        print(f"[run ] svd power step {tag}", flush=True)
        fn, args = fa()
        bad += check_variant_contract(tag, fn, args, mesh)
        out[tag] = analyze(jax.jit(fn).lower(*args))
        r = out[tag]
        print(f"[ ok ] {tag}: flops={r.get('flops', 0):.3e} "
              f"coll={r.get('collective_bytes_total', 0)/1e6:.1f}MB",
              flush=True)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(os.path.dirname(RESULTS_DIR.rstrip("/")),
                        "svd_dryrun.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print("written", path)
    if bad:
        print(f"svd_dryrun: {len(bad)} collective-contract violation(s) — "
              f"the lowered schedule drifted from the documented one",
              flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
