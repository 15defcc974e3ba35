"""FFN blocks: gated-linear-unit MLP (SwiGLU/GeGLU) and capacity-based MoE.

The MoE uses the standard dropped-token capacity dispatch (GShard/Switch
lineage) implemented with a shard_map over the mesh so the dispatch
scatter stays local to each data shard:

  * router -> top-k experts per token (+ load-balance aux loss)
  * per-shard position-in-expert via cumsum; tokens beyond the local
    capacity are dropped (standard; capacity_factor controls slack)
  * scatter to (E, C_local, D) -> batched expert GEMMs -> combine

Expert weights are stored FSDP-sharded on the embed dim (``data``) and
TP-sharded on the ffn dim (``model``): each chip holds a slice of every
expert, so even grok-1's 314B of experts fit.  Inside the shard_map the
embed shards are all-gathered just-in-time (explicit FSDP) and the
row-parallel output reduce is a single psum over ``model`` — the Megatron
schedule, expressed with jax collectives.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.models.config import ModelConfig


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


# ---------------------------------------------------------------------------
# Dense GLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig):
    D, F = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    dt = jnp.dtype(cfg.dtype)
    si, so = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    p = {
        "w_in": (jax.random.normal(k2, (D, F)) * si).astype(dt),
        "w_out": (jax.random.normal(k3, (F, D)) * so).astype(dt),
    }
    if cfg.mlp_variant == "glu":
        p["w_gate"] = (jax.random.normal(k1, (D, F)) * si).astype(dt)
    return p


def mlp_specs(cfg: ModelConfig):
    s = {
        "w_in": ("embed_p", "mlp"),
        "w_out": ("mlp", "embed_p"),
    }
    if cfg.mlp_variant == "glu":
        s["w_gate"] = ("embed_p", "mlp")
    return s


def apply_mlp(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    act = _act(cfg.mlp_act)
    if cfg.mlp_variant == "glu":
        h = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
        h = h * jnp.einsum("bsd,df->bsf", x, p["w_in"])
    else:
        h = act(jnp.einsum("bsd,df->bsf", x, p["w_in"]))
    h = sharding.constrain(h, "batch", None, "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_out"])
    return sharding.constrain(y, "batch", None, "embed")


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig):
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    si, so = 1.0 / math.sqrt(D), 1.0 / math.sqrt(F)
    return {
        "router": (jax.random.normal(k0, (D, E)) * si).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (E, D, F)) * si).astype(dt),
        "w_in": (jax.random.normal(k2, (E, D, F)) * si).astype(dt),
        "w_out": (jax.random.normal(k3, (E, F, D)) * so).astype(dt),
    }


def moe_specs(cfg: ModelConfig):
    return {
        "router": ("embed_p", "expert"),
        "w_gate": ("expert", "embed_p", "mlp"),
        "w_in": ("expert", "embed_p", "mlp"),
        "w_out": ("expert", "mlp", "embed_p"),
    }


def _moe_local(x, router, w_gate, w_in, w_out, *, cfg: ModelConfig,
               batch_axes: tuple[str, ...], data_axes: tuple[str, ...],
               tp_axis: str | None):
    """Per-shard MoE body (runs under shard_map).

    x: (B_loc, S, D) — full D.  Weights arrive sharded:
    router (D, E) replicated; w_* (E, D/|data|, F/|tp|).
    ``batch_axes`` shard the tokens (pod+data); ``data_axes`` shard the
    expert embed dim (FSDP storage, gathered just-in-time).
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)

    # ---- router (fp32) ----
    logits = xt.astype(jnp.float32) @ router            # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)     # (T, k)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e f_e * p_e, averaged globally
    me = jnp.mean(probs, axis=0)                                   # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)

    # ---- capacity dispatch (local to this shard) ----
    C = max(8, int(math.ceil(T * k / E * cfg.capacity_factor)))
    flat_expert = expert_ids.reshape(T * k)                        # slot-major? token-major
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)       # (T*k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot                 # (T*k, E)
    pos = jnp.sum(pos_in_e * onehot, axis=-1)                      # (T*k,)
    keep = pos < C
    slot = flat_expert * C + jnp.minimum(pos, C - 1)               # (T*k,)

    xk = jnp.repeat(xt, k, axis=0)                                 # (T*k, D)
    buf = jnp.zeros((E * C, D), xt.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xk, 0))
    buf = buf.reshape(E, C, D)

    # ---- explicit FSDP: gather expert weights' embed shards ----
    if data_axes:
        w_gate = jax.lax.all_gather(
            w_gate, data_axes, axis=1, tiled=True)
        w_in = jax.lax.all_gather(w_in, data_axes, axis=1, tiled=True)
    act = _act(cfg.mlp_act)
    h = act(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_in)                  # (E, C, F/tp)
    y = jnp.einsum("ecf,efd->ecd", h, w_out)                       # partial on D? no:
    # w_out arrives (E, F/|tp|, D/|data|): contraction over local F gives a
    # partial sum -> psum over tp; D is sharded over data, gather after.
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)
    if data_axes:
        y = jax.lax.all_gather(y, data_axes, axis=2, tiled=True)   # (E, C, D)

    # ---- combine back to tokens ----
    out_k = y.reshape(E * C, D)[slot]                              # (T*k, D)
    out_k = out_k * (keep[:, None] * gate_vals.reshape(T * k, 1))
    out = jnp.sum(out_k.reshape(T, k, D), axis=1)
    return out.reshape(B, S, D).astype(x.dtype), aux


def apply_moe(p, cfg: ModelConfig, x: jax.Array):
    """MoE FFN; returns (y, aux_loss). Runs per-shard via shard_map."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or not mesh.axis_names:
        # single-device path (tests)
        y, aux = _moe_local(x, p["router"], p["w_gate"], p["w_in"],
                            p["w_out"], cfg=cfg, batch_axes=(),
                            data_axes=(), tp_axis=None)
        return y, aux

    names = set(mesh.axis_names)
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    data_axes = tuple(a for a in ("data",) if a in names)  # FSDP storage axis
    tp_axis = "model" if "model" in names else None
    batch_ax = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    dshard = data_axes[0] if data_axes else None

    x_spec = P(batch_ax, None, None)
    r_spec = P(None, None)
    w_spec = P(None, dshard, tp_axis)
    wo_spec = P(None, tp_axis, dshard)

    fn = functools.partial(_moe_local, cfg=cfg, batch_axes=batch_axes,
                           data_axes=data_axes, tp_axis=tp_axis)
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, wo_spec),
        out_specs=(x_spec, P()),
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    return y, aux
