"""Compile the main path's programs for a described TPU v5e (no chip).

The TPU compiler is installed even where no chip is attached: it
compiles for a ``v5e:2x2`` topology that is only described, and refuses
what the chip would refuse (misaligned tiles, too much VMEM, programs
that do not fit HBM).  Nothing here runs; results and times need a chip.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process may load the TPU library, and
every test worker imports every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.compat import make_mesh
from repro.core.oom import hostblock_chain_step_fn
from repro.core.operator import (_dense_chain, fused_chain_tiles,
                                 sharded_gram_chain_fn)
from repro.core.precision import fp32_dots
from repro.kernels import ops

#: the paper's k and the dense per-chip shape (8 GiB fp32, half of HBM)
M, N, K = 65536, 32768, 32
#: the iterate's width: k plus the default oversample
L = K + 8
#: one v5e chip's HBM
HBM_BYTES = 16 << 30
DTYPES = ("float32", "bfloat16")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


@pytest.mark.parametrize("dtype", DTYPES)
def test_pallas_block_gram_chain_compiles(one_chip, dtype):
    fn = jax.jit(functools.partial(
        ops.block_gram_chain, interpret=False,
        dtype=None if dtype == "float32" else dtype))
    compiled = fn.lower(_spec((M, N), one_chip),
                        _spec((N, K), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _hbm_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("dtype", DTYPES)
def test_dense_chain_fits_one_chip(one_chip, dtype):
    with fp32_dots():
        compiled = _dense_chain.lower(_spec((M, N), one_chip),
                                      _spec((N, K), one_chip),
                                      sweep_dtype=dtype).compile()
    assert _hbm_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("dtype", DTYPES)
def test_dense_chain_fused_reads_a_in_place(one_chip, dtype, monkeypatch):
    """On a TPU the chain program is still ``jit__dense_chain``, now
    around the one-read kernel, and keeps no A-sized temporary (a bf16
    copy of ``A`` would be the smallest)."""
    tiles = fused_chain_tiles("tpu", (M, N), L, dtype)
    assert tiles is not None
    # the default backend here is the CPU, which would interpret
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with fp32_dots():
        compiled = _dense_chain.lower(_spec((M, N), one_chip),
                                      _spec((N, L), one_chip),
                                      sweep_dtype=dtype,
                                      tiles=tiles).compile()
    text = compiled.as_text()
    assert re.search(r"^HloModule jit__dense_chain\b", text, re.M)
    assert "tpu_custom_call" in text
    assert compiled.memory_analysis().temp_size_in_bytes < M * N * 2
    assert _hbm_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("dtype", DTYPES)
def test_hostblock_chain_step_compiles(one_chip, dtype):
    rows = 163840 // 32                    # the smoke's streamed block
    with fp32_dots():
        compiled = hostblock_chain_step_fn(dtype).lower(
            _spec((N, K), one_chip), _spec((rows, N), one_chip, dtype),
            _spec((N, K), one_chip)).compile()
    assert _hbm_bytes(compiled) < HBM_BYTES


def test_sharded_gram_chain_has_one_all_reduce(topo):
    mesh = make_mesh((4,), ("data",), devices=topo.devices[:4])
    row, rep = NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P())
    with fp32_dots():
        compiled = sharded_gram_chain_fn(mesh, ("data",), "float32").lower(
            _spec((4 * M, N), row), _spec((N, K), rep)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\ball-reduce(?:-start)?\(", text)) == 1
    assert _hbm_bytes(compiled) < HBM_BYTES     # per device: its slab
