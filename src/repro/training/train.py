"""Train step assembly: microbatching, gradient sync, SVD compression.

Two gradient-synchronization modes:

* **plain** — params replicated across ``pod``; GSPMD emits the cross-pod
  all-reduce of full gradients as part of the backward pass.
* **compressed** (the paper's technique as a distributed-optimization
  trick) — forward/backward run inside a shard_map that is *manual over
  the pod axis only* (data/model stay GSPMD-auto).  Each pod produces its
  local gradients; only the rank-r power-method factors cross the DCI
  links (see repro.optim.compression); error feedback keeps training
  unbiased.  Every pod then applies the identical update, keeping params
  bitwise-replicated across pods.

Microbatching: ``lax.scan`` over microbatches accumulating fp32 grads —
bounds activation memory at large global batch (the 1M-token train_4k
cells need it).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat, sharding
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adamw as opt
from repro.optim import compression as comp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    adamw: opt.AdamWConfig = opt.AdamWConfig()
    compression: comp.CompressionConfig = comp.CompressionConfig(enabled=False)
    microbatches: int = 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt: PyTree
    comp: PyTree | None
    step: jax.Array


def init_train_state(key, cfg: ModelConfig, tc: TrainConfig,
                     mesh: Mesh | None = None) -> TrainState:
    params = T.init_model(key, cfg)
    o = opt.init_opt_state(params, tc.adamw)
    c = None
    if tc.compression.enabled:
        c = comp.init_state(params, tc.compression)
        if mesh is not None and "pod" in mesh.axis_names:
            # error-feedback buffers are PER-POD state (PowerSGD
            # semantics): store them stacked over the pod axis
            npods = mesh.shape["pod"]
            c["err"] = jax.tree.map(
                lambda e: (e if isinstance(e, tuple) else
                           jnp.broadcast_to(e[None], (npods,) + e.shape)),
                c["err"], is_leaf=lambda x: isinstance(x, tuple))
    return TrainState(params=params, opt=o, comp=c,
                      step=jnp.zeros((), jnp.int32))


def train_state_specs(cfg: ModelConfig, tc: TrainConfig):
    """Logical-axis tree for the whole TrainState (ckpt/sharding reuse)."""
    pspecs = T.model_specs(cfg)
    ospecs = {"m": pspecs, "v": pspecs, "count": ()}
    cspecs = None
    if tc.compression.enabled:
        # Q/err follow their parameter's sharding loosely; replicate Q
        # (skinny) and shard err like the param.
        cspecs = {
            "Q": jax.tree.map(lambda _: (None, None), pspecs,
                              is_leaf=lambda x: isinstance(x, tuple)),
            "err": pspecs,
        }
    return TrainState(params=pspecs, opt=ospecs, comp=cspecs, step=())


def _microbatch(batch: PyTree, n: int) -> PyTree:
    """(B, ...) -> (n, B//n, ...) on every leaf."""
    def r(x):
        B = x.shape[0]
        return x.reshape(n, B // n, *x.shape[1:])
    return jax.tree.map(r, batch)


def _grads_and_metrics(params, cfg: ModelConfig, batch, n_micro: int):
    """fp32-accumulated grads over microbatches."""
    def loss_fn(p, mb):
        return T.loss_fn(p, cfg, mb)

    if n_micro == 1:
        (loss, m), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return grads, {"loss": m["loss"], "aux": m["aux"]}

    mbatch = _microbatch(batch, n_micro)
    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def body(carry, mb):
        acc, loss_acc = carry
        (loss, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32), acc, g)
        return (acc, loss_acc + m["loss"]), None

    init = (g0, jnp.float32(0))
    # Inside a partial-manual shard_map (pod-compressed mode) the per-pod
    # grads/loss are mesh-varying; mark the scan init to match.
    am = jax.sharding.get_abstract_mesh()
    if am is not None and not am.empty:
        manual = tuple(n for n, t in zip(am.axis_names,
                                         getattr(am, "axis_types", ()))
                       if "Manual" in str(t))
        if manual:
            init = compat.pvary(init, manual)
    (gsum, loss_sum), _ = jax.lax.scan(body, init, mbatch)
    grads = jax.tree.map(lambda g: (g / n_micro), gsum)
    return grads, {"loss": loss_sum / n_micro,
                   "aux": jnp.zeros((), jnp.float32)}


def make_train_step(cfg: ModelConfig, tc: TrainConfig, mesh: Mesh | None):
    """Returns jit-able ``step(state, batch) -> (state, metrics)``."""
    use_pod_compression = (
        tc.compression.enabled and mesh is not None
        and "pod" in mesh.axis_names)

    if not use_pod_compression:
        def step(state: TrainState, batch):
            grads, metrics = _grads_and_metrics(
                state.params, cfg, batch, tc.microbatches)
            cstate = state.comp
            if tc.compression.enabled:
                grads, cstate, cs = comp.compress_grads(
                    grads, dict(cstate), tc.compression, axis_name=None)
                metrics.update(cs)
            params, ostate, om = opt.apply_updates(
                state.params, grads, state.opt, tc.adamw)
            metrics.update(om)
            return TrainState(params=params, opt=ostate, comp=cstate,
                              step=state.step + 1), metrics
        return step

    # ---- cross-pod compressed mode -------------------------------------
    _istuple = lambda x: isinstance(x, tuple)

    def per_pod(params, ostate, cstate, step_ct, batch):
        # unstack this pod's error-feedback slice: (1, ...) -> (...)
        cstate = dict(cstate)
        cstate["err"] = jax.tree.map(
            lambda e: e if isinstance(e, tuple) else e[0],
            cstate["err"], is_leaf=_istuple)
        grads, metrics = _grads_and_metrics(params, cfg, batch,
                                            tc.microbatches)
        # mean loss across pods for reporting
        metrics = {k: jax.lax.pmean(v, "pod") for k, v in metrics.items()}
        grads, cstate, cs = comp.compress_grads(
            grads, cstate, tc.compression, axis_name="pod")
        metrics.update(cs)
        params, ostate, om = opt.apply_updates(params, grads, ostate,
                                               tc.adamw)
        metrics.update(om)
        cstate = dict(cstate)
        cstate["err"] = jax.tree.map(
            lambda e: e if isinstance(e, tuple) else e[None],
            cstate["err"], is_leaf=_istuple)
        return params, ostate, cstate, step_ct + 1, metrics

    def step(state: TrainState, batch):
        # empty-tuple ("not compressed") leaves keep their () structure
        repl = lambda tree: jax.tree.map(
            lambda e: () if isinstance(e, tuple) else P(), tree,
            is_leaf=_istuple)
        batch_spec = jax.tree.map(lambda _: P("pod"), batch)
        comp_spec = {"Q": repl(state.comp["Q"]),
                     "err": jax.tree.map(
                         lambda e: () if isinstance(e, tuple) else P("pod"),
                         state.comp["err"], is_leaf=_istuple)}
        params, ostate, cstate, step_ct, metrics = jax.shard_map(
            per_pod,
            mesh=mesh,
            in_specs=(repl(state.params), repl(state.opt),
                      comp_spec, P(), batch_spec),
            out_specs=(repl(state.params), repl(state.opt),
                       comp_spec, P(),
                       {k: P() for k in ["loss", "aux", "compress_ratio",
                                         "grad_norm", "lr"]}),
            axis_names=frozenset({"pod"}),
        )(state.params, state.opt, state.comp, state.step, batch)
        return TrainState(params=params, opt=ostate, comp=cstate,
                          step=step_ct), metrics

    return step
