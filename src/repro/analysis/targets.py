"""The analyzer's target registry: the solver's OWN step functions.

One ``StepTarget`` per (backend x config) cell — the jitted callables
``core/svd.py`` actually dispatches (``dense_block_step_fn``,
``sharded_block_step_fn``, ``hostblock_chain_step_fn``, ...), traced at
small shapes with ``ShapeDtypeStruct`` inputs.  Because the targets ARE
the driver's builders (not re-derived copies), a schedule regression in
the solver fails the analyzer by construction.

Alongside the traces, ``AccountingGroup``s pin the static byte
estimates to the runtime accounting: each group names the step traces
whose A-traffic, summed (x ``replicas`` shards), must equal
``chain_passes * bytes_per_pass`` of a REAL operator instance built at
the same shapes.  The numpy-streamed backends, which have no jaxpr to
trace, contribute metadata groups (``nnz * itemsize`` vs the operator's
``bytes_per_pass``) plus the shared jax extraction trace.

Coverage (all six backends, per-config):

=============  ==========================================================
dense          block step + sketch + extract, fp32/bf16, dots accounting;
               the fused Pallas block step (fp32, interpret mode)
sharded        block step fp32/bf16 (twin-paired: identical collective
               bytes), warm sketch, extract, deflation faithful (3
               psums) vs opt (1 fused psum)
hostblocked    per-block fused chain steps fp32/bf16, sketch step,
               staged-bytes accounting
memmap         the SAME inherited device-side steps (tagged) + a real
               ``MemmapMatrix`` accounting group over a temp ``.npy``
sparsestream   metadata accounting + the shared extraction trace
scipysparse    metadata accounting over a real scipy CSR
kernels        the Pallas fused-chain wrapper under bf16 operands
=============  ==========================================================
"""
from __future__ import annotations

import dataclasses
import functools

from repro.analysis.jaxpr_check import StepContract

# Small trace shapes: tracing cost only, no solve.  M is divisible by
# 1 and 8 host devices and by the 3-block staging plan.
M, N, K = 384, 160, 8
N_TILED = 256             # a width the fused dense chain tiles (n % 128 == 0)
L = K + 8                 # oversampled sketch width (k + default oversample)
N_BLOCKS = 3


@dataclasses.dataclass
class StepTarget:
    tag: str                      # "sharded/block/bf16"
    backend: str
    fn: object                    # traceable callable
    args: tuple                   # ShapeDtypeStructs / concrete arrays
    contract: StepContract | None = None
    group: str | None = None      # AccountingGroup name
    a_nbytes: int | None = None   # A-operand bytes in THIS trace
    note: str = ""


@dataclasses.dataclass
class AccountingGroup:
    name: str                     # "dense/chain/fp32"
    mode: str                     # "dots" | "staged" | "meta"
    expected_bytes: int           # passes * bytes_per_pass (live operator)
    source: str                   # where expected_bytes came from
    replicas: int = 1             # sharded: per-shard trace x n shards
    measured_bytes: int | None = None   # pre-measured (meta groups only)


def _sds(shape, dtype):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _dense_targets():
    import jax.numpy as jnp
    from repro.core.config import seed_to_key
    from repro.core.operator import (DenseOperator, _dense_extract,
                                     _dense_sketch, dense_block_step_fn)
    from repro.kernels.block_matvec import chain_tiles

    targets, groups = [], []
    for sd, itm in (("float32", 4), ("bfloat16", 2)):
        op = DenseOperator(jnp.zeros((M, N), jnp.float32), sweep_dtype=sd)
        groups.append(AccountingGroup(
            f"dense/chain/{sd}", "dots",
            op.chain_passes * op.bytes_per_pass,
            f"DenseOperator.chain_passes({op.chain_passes}) * "
            f"bytes_per_pass({op.bytes_per_pass})"))
        targets.append(StepTarget(
            f"dense/block/{sd}", "dense",
            dense_block_step_fn(sd),
            (_sds((M, N), "float32"), _sds((N, K), "float32")),
            StepContract(requires_bf16=(sd == "bfloat16")),
            group=f"dense/chain/{sd}", a_nbytes=M * N * itm))
    # the one-read Pallas chain a TPU runs (interpreted here), at a
    # width that tiles: the kernel reads A once, as one pallas_call
    # operand, so a fused operator counts chain_passes = 1
    tiles = chain_tiles(M, N_TILED, K, "float32")
    opf = DenseOperator(jnp.zeros((M, N_TILED), jnp.float32))
    groups.append(AccountingGroup(
        "dense/chain/fused/float32", "dots", 1 * opf.bytes_per_pass,
        f"fused chain_passes(1) * bytes_per_pass({opf.bytes_per_pass})"))
    targets.append(StepTarget(
        "dense/block/fused/float32", "dense",
        dense_block_step_fn("float32", tiles),
        (_sds((M, N_TILED), "float32"), _sds((N_TILED, K), "float32")),
        StepContract(), group="dense/chain/fused/float32",
        a_nbytes=M * N_TILED * 4,
        note=f"Pallas chain, tiles {tiles}, interpret mode"))
    op32 = DenseOperator(jnp.zeros((M, N), jnp.float32))
    groups.append(AccountingGroup(
        "dense/sketch/float32", "dots",
        op32.sketch_passes * op32.bytes_per_pass,
        f"DenseOperator.sketch_passes({op32.sketch_passes}) * "
        f"bytes_per_pass({op32.bytes_per_pass})"))
    targets.append(StepTarget(
        "dense/sketch/warm", "dense",
        functools.partial(_dense_sketch, l=L, sweep_dtype="float32"),
        (_sds((M, N), "float32"), seed_to_key(0)),
        StepContract(),
        group="dense/sketch/float32", a_nbytes=M * N * 4))
    targets.append(StepTarget(
        "dense/extract", "dense", _dense_extract,
        (_sds((M, N), "float32"), _sds((N, K), "float32")),
        StepContract(), note="fp32 Rayleigh-Ritz extraction pass"))
    return targets, groups, []


def _make_mesh():
    import jax
    from repro.compat import make_mesh
    ndev = len(jax.devices())
    return make_mesh((ndev,), ("data",)), ndev


def _sharded_targets():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.dist_svd import _deflated_chain_step
    from repro.core.operator import (ShardedOperator, sharded_block_step_fn,
                                     sharded_extract_fn, sharded_sketch_fn)

    mesh, ndev = _make_mesh()
    axes = ("data",)
    m_loc = M // ndev
    targets, groups, twins = [], [], []

    for sd, itm in (("float32", 4), ("bfloat16", 2)):
        op = ShardedOperator(jnp.zeros((M, N), jnp.float32), mesh, axes,
                             sweep_dtype=sd)
        groups.append(AccountingGroup(
            f"sharded/chain/{sd}", "dots",
            op.chain_passes * op.bytes_per_pass,
            f"ShardedOperator.chain_passes({op.chain_passes}) * "
            f"bytes_per_pass({op.bytes_per_pass})",
            replicas=ndev))
        targets.append(StepTarget(
            f"sharded/block/{sd}", "sharded",
            sharded_block_step_fn(mesh, axes, sd),
            (_sds((M, N), "float32"), _sds((N, K), "float32")),
            StepContract(psum_payloads=(((N, K),),),
                         requires_bf16=(sd == "bfloat16")),
            group=f"sharded/chain/{sd}", a_nbytes=m_loc * N * itm))
    twins.append(("sharded/block/float32", "sharded/block/bfloat16"))

    op32 = ShardedOperator(jnp.zeros((M, N), jnp.float32), mesh, axes)
    groups.append(AccountingGroup(
        "sharded/sketch/float32", "dots",
        op32.sketch_passes * op32.bytes_per_pass,
        f"ShardedOperator.sketch_passes({op32.sketch_passes}) * "
        f"bytes_per_pass({op32.bytes_per_pass})",
        replicas=ndev))
    targets.append(StepTarget(
        "sharded/sketch/warm", "sharded",
        sharded_sketch_fn(mesh, axes, L, "float32"),
        (_sds((M, N), "float32"), _sds((1,), "uint32")),
        StepContract(psum_payloads=(((N, L),),)),
        group="sharded/sketch/float32", a_nbytes=m_loc * N * 4))
    targets.append(StepTarget(
        "sharded/extract", "sharded",
        sharded_extract_fn(mesh, axes),
        (_sds((M, N), "float32"), _sds((N, K), "float32")),
        StepContract(psum_payloads=(((K, K),),)),
        note="Rayleigh-Ritz via the psum'd (k, k) Gram"))

    # The deflation engine's power step, paper-faithful (3 all-reduces,
    # Alg 4 lines 6/8/16) vs optimized (ONE fused concatenated psum).
    row = P("data", None)

    def deflation_step(faithful):
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(row, row, P(None), P(None, None), P(None)),
            out_specs=P(None))
        def power_step(A_loc, U_loc, S, V, v):
            v1 = _deflated_chain_step(A_loc, U_loc, S, V, v, axes,
                                      faithful=faithful, n_blocks=1)
            return v1 / jnp.sqrt(jnp.sum(v1 * v1))
        return jax.jit(power_step)

    defl_args = (_sds((M, N), "float32"), _sds((M, K), "float32"),
                 _sds((K,), "float32"), _sds((N, K), "float32"),
                 _sds((N,), "float32"))
    targets.append(StepTarget(
        "sharded/deflation/faithful", "sharded", deflation_step(True),
        defl_args,
        StepContract(psum_payloads=(((N,),), ((K,),), ((N,),))),
        note="paper Alg-4 schedule: psums of t1 (n,), UtXv (k,), t3 (n,)"))
    targets.append(StepTarget(
        "sharded/deflation/opt", "sharded", deflation_step(False),
        defl_args,
        StepContract(psum_payloads=(((N + K,),),)),
        note="fused sweep: ONE concatenated (n+k,) all-reduce per step"))
    return targets, groups, twins


def _hostblocked_targets():
    import numpy as np
    from repro.core.oom import (HostBlockedMatrix, hostblock_chain_step_fn,
                                hostblock_sketch_step_fn)
    from repro.core.operator import HostBlockedOperator

    targets, groups = [], []
    A = np.zeros((M, N), np.float32)
    for sd, itm in (("float32", 4), ("bfloat16", 2)):
        host = HostBlockedMatrix(A, N_BLOCKS, stage_dtype=sd)
        op = HostBlockedOperator(host)
        groups.append(AccountingGroup(
            f"hostblocked/chain/{sd}", "staged",
            op.chain_passes * op.bytes_per_pass,
            f"HostBlockedOperator.chain_passes({op.chain_passes}) * "
            f"bytes_per_pass({op.bytes_per_pass})"))
        for b in range(host.n_blocks):
            lo, hi = host.plan.bounds(b)
            rows = hi - lo
            targets.append(StepTarget(
                f"hostblocked/chain/{sd}/block{b}", "hostblocked",
                hostblock_chain_step_fn(sd),
                (_sds((N, K), "float32"), _sds((rows, N), sd),
                 _sds((N, K), "float32")),
                StepContract(requires_bf16=(sd == "bfloat16")),
                group=f"hostblocked/chain/{sd}", a_nbytes=rows * N * itm))
    targets.append(StepTarget(
        "hostblocked/sketch/step", "hostblocked",
        hostblock_sketch_step_fn(),
        (_sds((N, L), "float32"), _sds((M // N_BLOCKS, N), "float32"),
         _sds((M // N_BLOCKS, L), "float32")),
        StepContract(),
        note="one block of the streamed range sketch (Omega on the fly)"))
    return targets, groups, []


def _memmap_targets():
    import os
    import shutil
    import tempfile

    import numpy as np
    from repro.core.diskio import MemmapMatrix
    from repro.core.oom import hostblock_chain_step_fn
    from repro.core.operator import MemmapOperator

    # A real (tiny, temporary) .npy so the accounting group pins the
    # ACTUAL MemmapMatrix/MemmapOperator byte arithmetic, not a copy of
    # its formula.  The device-side step is class-inherited from
    # HostBlockedMatrix — the trace below IS the memmap backend's step.
    tmp = tempfile.mkdtemp(prefix="repro_analysis_")
    try:
        path = os.path.join(tmp, "a.npy")
        np.save(path, np.zeros((M, N), np.float32))
        host = MemmapMatrix(np.load(path, mmap_mode="r"), N_BLOCKS,
                            stage_dtype="bfloat16")
        op = MemmapOperator(host)
        expected = op.chain_passes * op.bytes_per_pass
        n_blocks = host.n_blocks
        bounds = [host.plan.bounds(b) for b in range(n_blocks)]
        src = (f"MemmapOperator.chain_passes({op.chain_passes}) * "
               f"bytes_per_pass({op.bytes_per_pass})")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    targets = []
    groups = [AccountingGroup("memmap/chain/bfloat16", "staged",
                              expected, src)]
    for b, (lo, hi) in enumerate(bounds):
        rows = hi - lo
        targets.append(StepTarget(
            f"memmap/chain/bfloat16/block{b}", "memmap",
            hostblock_chain_step_fn("bfloat16"),
            (_sds((N, K), "float32"), _sds((rows, N), "bfloat16"),
             _sds((N, K), "float32")),
            StepContract(requires_bf16=True),
            group="memmap/chain/bfloat16", a_nbytes=rows * N * 2,
            note="inherited HostBlockedMatrix step; disk->host staging "
                 "is host-side (covered by the lint pass)"))
    return targets, groups, []


def _sparse_targets():
    import jax
    import numpy as np
    import scipy.sparse

    from repro.core.sparse import ScipySparseMatrix, SyntheticSparseMatrix
    from repro.core.operator import SparseStreamOperator
    from repro.core.sparse import ScipySparseOperator
    from repro.core.tsvd import rayleigh_ritz_from_W

    groups = []
    syn = SyntheticSparseMatrix(M, N, 4, seed=0)
    for sd, itm in (("float32", 4), ("bfloat16", 2)):
        op = SparseStreamOperator(syn, sweep_dtype=sd)
        groups.append(AccountingGroup(
            f"sparsestream/meta/{sd}", "meta",
            syn.nnz * itm, f"nnz({syn.nnz}) * itemsize({itm})",
            measured_bytes=op.chain_passes * op.bytes_per_pass))

    sp = scipy.sparse.random(M, N, density=0.05, format="csr",
                             random_state=0, dtype=np.float32)
    scp = ScipySparseMatrix(sp, seed=0)
    sop = ScipySparseOperator(scp)
    groups.append(AccountingGroup(
        "scipysparse/meta/float32", "meta",
        int(sp.nnz) * 4, f"scipy nnz({int(sp.nnz)}) * itemsize(4)",
        measured_bytes=sop.chain_passes * sop.bytes_per_pass))

    # The one jax stage both sparse backends share: the fp32 extraction.
    targets = [StepTarget(
        "sparsestream/extract", "sparsestream",
        jax.jit(rayleigh_ritz_from_W),
        (_sds((M, K), "float32"), _sds((N, K), "float32")),
        StepContract(),
        note="host-streamed backends lift W, Q into jax for extraction")]
    return targets, groups, []


def _kernel_targets():
    from repro.kernels import ops

    return [StepTarget(
        "kernels/block_gram_chain/bfloat16", "kernels",
        functools.partial(ops.block_gram_chain, interpret=True),
        (_sds((M, N), "bfloat16"), _sds((N, K), "bfloat16")),
        StepContract(requires_bf16=True),
        note="fused Pallas A^T(A Q): bf16 tiles must accumulate fp32 "
             "inside the kernel body (walked through pallas_call)")],\
        [], []


def build_targets():
    """All step targets + accounting groups + bf16 twin pairs.

    Returns ``(targets, groups, twins)`` where ``twins`` are pairs of
    target tags whose traced collective bytes must be IDENTICAL (the
    bf16 sweep halves HBM traffic, never collective payloads).
    """
    targets, groups, twins = [], [], []
    for builder in (_dense_targets, _sharded_targets, _hostblocked_targets,
                    _memmap_targets, _sparse_targets, _kernel_targets):
        t, g, w = builder()
        targets.extend(t)
        groups.extend(g)
        twins.extend(w)
    return targets, groups, twins
