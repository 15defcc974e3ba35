"""Pallas TPU kernels for the paper's compute hot spots.

* ``gram``            — tiled ``A^T A`` (paper Alg 3: batch/tile + symmetric tasks)
* ``deflate_matvec``  — fused Alg-4 deflated power step sweeps
* ``block_matvec``    — multi-vector ``A Q`` / ``A^T Y`` sweeps for the
                        block subspace-iteration method (k columns per
                        pass over A), and their chain ``A^T (A Q)`` in
                        one read of A; takes the ``sweep_dtype``
                        policy's ``dtype`` (bf16 operands, fp32
                        accumulation)
* ``local_attn``      — causal sliding-window flash attention (serving hot spot)

Each kernel has a pure-jnp oracle in ``ref.py``; ``ops.py`` is the jit'd
public wrapper (padding + CPU interpret fallback).
"""
from repro.kernels.ops import (  # noqa: F401
    gram,
    matvec,
    block_matvec,
    block_rmatvec,
    block_gram_chain,
    deflate_rmatvec,
    local_attention,
    gram_ref,
    matvec_ref,
    block_matvec_ref,
    block_rmatvec_ref,
    block_gram_chain_ref,
    deflate_rmatvec_ref,
    local_attention_ref,
)
