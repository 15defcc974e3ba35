"""The solver's profiler spans and the names of its device programs.

The benchmark's per-layer readers (``bench/metrics/*.py``) find host
work by the span names ``repro.core.spans`` lists and device work by the
jitted function a program was built from (module ``jit_<name>``).  These
tests pin both on the CPU: a renamed span or program fails here instead
of silently nulling a metric on the chip.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import svd
from repro.core.oom import (hostblock_chain_step_fn,
                            hostblock_deflate_step_fn,
                            hostblock_gram_step_fn, hostblock_matmat_fn,
                            hostblock_matvec_fn, hostblock_rmatmat_step_fn,
                            hostblock_sketch_step_fn)
from repro.core.operator import (_dense_chain, _dense_extract, _gap, _orth,
                                 sharded_extract_fn, sharded_gram_chain_fn)
from repro.core.spans import SPANS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src", "repro")
sys.path.insert(0, ROOT)

from bench import harness, trace_reduce  # noqa: E402


def _sources():
    for dirpath, _, files in os.walk(SRC):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    yield fh.read()


_SITE = re.compile(r"""\bspan\(\s*["']([^"']+)["']\s*\)""")


@pytest.mark.parametrize("name", sorted(SPANS))
def test_every_listed_span_is_opened_at_a_site(name):
    sites = [n for text in _sources() for n in _SITE.findall(text)]
    assert name in sites


def test_every_site_opens_a_listed_span():
    sites = {n for text in _sources() for n in _SITE.findall(text)}
    assert sites and sites <= set(SPANS)


# -- the spans of one streamed solve -----------------------------------------

@pytest.fixture(scope="module")
def hostblocked_trace(tmp_path_factory):
    """A tiny host-blocked solve (256 x 64, k = 4, 4 blocks, 3 forced
    iterations) traced by the profiler, after an untraced warm-up."""
    A = np.random.default_rng(0).standard_normal((256, 64)).astype(
        np.float32)
    kw = dict(n_blocks=4, max_iters=3, force_iters=True)
    svd(A, 4, **kw)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        res = svd(A, 4, **kw)
    return res, trace_reduce.load(log_dir)


@pytest.mark.parametrize("name, count", [
    ("svd.solve", 1), ("svd.iter", 3), ("op.chain", 3), ("svd.extract", 1),
    # force_iters reads nothing back from the device
    ("svd.sync", 0)])
def test_a_streamed_solve_opens_each_span(hostblocked_trace, name, count):
    _, tr = hostblocked_trace
    assert len(tr.spans(name)) == count


def test_a_streamed_solve_stages_every_block_of_every_pass(
        hostblocked_trace):
    res, tr = hostblocked_trace
    assert res.passes_over_A == 4                # 3 chains + the extraction
    assert len(tr.spans("stage.h2d")) == res.passes_over_A * 4
    # one pacing wait before every block's copy but the first of a pass
    assert len(tr.spans("stage.pace")) == res.passes_over_A * 3


def _inside(inner, outers):
    return any(o.start <= inner.start and inner.end <= o.end for o in outers)


def test_staging_spans_nest_in_a_sweep_inside_the_solve(hostblocked_trace):
    _, tr = hostblocked_trace
    sweeps = tr.spans("op.chain") + tr.spans("svd.extract")
    staged = tr.spans("stage.h2d") + tr.spans("stage.pace")
    assert staged
    for ev in staged:
        assert _inside(ev, sweeps), ev
    for ev in staged + sweeps + tr.spans("svd.iter"):
        assert _inside(ev, tr.spans("svd.solve")), ev


def test_wall_time_covers_the_factors(hostblocked_trace):
    res, tr = hostblocked_trace
    (solve,) = tr.spans("svd.solve")
    assert 0 < res.wall_time_s <= (solve.end - solve.start) / 1e9


# -- the program names the readers search for ------------------------------

def _lowered(name):
    """The program of ``name`` lowered on tiny shapes."""
    X = jnp.ones((64, 16), jnp.float32)
    Q = jnp.ones((16, 4), jnp.float32)
    blk, acc = X[:16], jnp.zeros((16, 4), jnp.float32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    return {
        "_dense_chain": lambda: _dense_chain.lower(X, Q,
                                                   sweep_dtype="float32"),
        "_orth": lambda: _orth.lower(X),
        "_gap": lambda: _gap.lower(Q, Q),
        "_dense_extract": lambda: _dense_extract.lower(X, Q),
        "_step": lambda: hostblock_chain_step_fn("float32").lower(
            acc, blk, Q),
        "gram_chain": lambda: sharded_gram_chain_fn(
            mesh, ("data",), "float32").lower(X, Q),
        "extract": lambda: sharded_extract_fn(mesh, ("data",)).lower(X, Q),
        "hostblock_gram_step": lambda: hostblock_gram_step_fn().lower(
            jnp.zeros((16, 16)), blk),
        "hostblock_matvec": lambda: hostblock_matvec_fn().lower(
            blk, Q[:, 0]),
        "hostblock_matmat": lambda: hostblock_matmat_fn().lower(blk, Q),
        "hostblock_rmatmat_step": lambda: hostblock_rmatmat_step_fn().lower(
            acc, blk, blk[:, :4]),
        "hostblock_sketch_step": lambda: hostblock_sketch_step_fn().lower(
            acc, blk, blk[:, :4]),
        "hostblock_deflate_step": lambda: hostblock_deflate_step_fn().lower(
            acc[:, 0], blk, blk[:, 0], blk[:, :4], Q[:4, 0]),
    }[name]()


def _module_name(lowered):
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


_READ = sorted(set(harness.load_metric("sweep_roofline").PROGRAMS)
               | set(harness.load_metric("small_ops_share").PROGRAMS))


@pytest.mark.parametrize("name", _READ + [
    "hostblock_gram_step", "hostblock_matvec", "hostblock_matmat",
    "hostblock_rmatmat_step", "hostblock_sketch_step",
    "hostblock_deflate_step"])
def test_a_program_is_named_after_its_function(name):
    module = _module_name(_lowered(name))
    assert module == "jit_" + name
    assert trace_reduce.program(module + "(7)") == name

