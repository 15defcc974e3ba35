"""Operator sweep: the gram chain ``A^T (A Q)``'s share of its roofline.

Reads the device trace.  The chain is the program built from one of
``PROGRAMS``: ``_dense_chain`` (one device), ``gram_chain`` (the
sharded ``shard_map`` chain, per chip) and ``_step`` (one host block of
the streamed chain).  The least time one call could take on a chip is
the larger of

* memory: one read of the rows of ``A`` the call covers, at ``A``'s
  stored dtype, plus ``Q`` in and ``Z`` out in float32, over the HBM
  peak;
* compute: ``4 * rows * n * k`` FLOP over the compute peak of the sweep
  dtype (for float32 at ``Precision.HIGHEST``, the bf16 peak over the
  number of bf16 passes ``peaks.json`` assumes).

The share is that least time, times the calls, over the device time of
the calls, per chip, averaged over the chips.  One read of ``A`` per
chain is what the algorithm needs, whatever implements it: a chain that
reads ``A`` twice reads at most 50%.
"""
import numpy as np

from bench import trace_reduce

PROGRAMS = ("_dense_chain", "gram_chain", "_step")


def rows_per_call(config: dict) -> int:
    """Rows of ``A`` one chain call reads on one chip."""
    if config["tier"] == "sharded":
        return config["m"] // config["chips"]
    if config["tier"] == "host":
        return config["m"] // config["n_blocks"]
    return config["m"]


def least_seconds(config: dict, peaks: dict) -> float:
    rows, n, k = rows_per_call(config), config["n"], config["k"]
    nbytes = rows * n * np.dtype(config["storage_dtype"]).itemsize \
        + 2 * n * k * 4
    flops = 4 * rows * n * k
    peak = peaks["bf16_flops_per_s"]
    if config["sweep_dtype"] == "float32":
        peak /= peaks["fp32_highest_bf16_passes"]
    return max(nbytes / peaks["hbm_bytes_per_s"], flops / peak)


def read(run):
    if run.trace is None:
        return None
    shares = []
    for dev in run.trace.devices:
        ns, calls = trace_reduce.program_ns(dev, PROGRAMS, run.lo, run.hi)
        if calls:
            least = least_seconds(run.config, run.peaks())
            shares.append(100.0 * calls * least * 1e9 / ns)
    return sum(shares) / len(shares) if shares else None
