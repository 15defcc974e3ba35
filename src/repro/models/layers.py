"""Shared transformer layers: norms, RoPE, GQA attention (global/local).

Plain-function modules over dict pytrees: ``init_*`` builds params,
``*_specs`` builds the matching logical-axis tree (see repro.sharding),
``apply_*`` runs the math.  Everything is GSPMD-friendly einsum code with
explicit logical sharding constraints; the Pallas flash kernel
(repro.kernels.local_attn) is the TPU execution path for the same math and
is cross-checked in tests.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro import sharding
from repro.models.config import ModelConfig


def _axis_size(name: str) -> int:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty or name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int):
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm_specs():
    return {"scale": ("embed_p",)}


def apply_rmsnorm(p, x, eps: float):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale) so zero-init is identity
    return (y * (1.0 + p["scale"])).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D) with even D; positions: (B, S) int32."""
    B, S, H, D = x.shape
    half = D // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (global causal or sliding-window local, GQA, qk-norm, softcap)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig):
    D = cfg.d_model
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale_in = 1.0 / math.sqrt(D)
    scale_out = 1.0 / math.sqrt(H * Dh)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "wq": (jax.random.normal(k1, (D, H, Dh)) * scale_in).astype(dt),
        "wk": (jax.random.normal(k2, (D, Hkv, Dh)) * scale_in).astype(dt),
        "wv": (jax.random.normal(k3, (D, Hkv, Dh)) * scale_in).astype(dt),
        "wo": (jax.random.normal(k4, (H, Dh, D)) * scale_out).astype(dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((Dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((Dh,), jnp.float32)
    return p


def attention_specs(cfg: ModelConfig):
    s = {
        "wq": ("embed_p", "heads", "qkv"),
        "wk": ("embed_p", "kv_heads", "qkv"),
        "wv": ("embed_p", "kv_heads", "qkv"),
        "wo": ("heads", "qkv", "embed_p"),
    }
    if cfg.qk_norm:
        s["q_norm"] = ("qkv",)
        s["k_norm"] = ("qkv",)
    return s


def _qk_normalize(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale)).astype(x.dtype)


def _constrain_attn(t, H, kind: str):
    """Shard attention tensors by heads when divisible, else by q-seq.

    K/V are expanded to the full H query heads *before* the score einsum
    precisely so this constraint lands on a divisible axis (kv_heads=8
    does not divide the 16-wide model axis; H=16/32/48 does).  llava-next's
    56 heads divide nothing — the query sequence is sharded over `model`
    instead (context-parallel style), which keeps the big score tensor
    distributed without changing the math.
    """
    tp = _axis_size("model")
    by_heads = (H % tp == 0)
    if kind == "scores":  # (B, H, Sq, Skv)
        if by_heads:
            return sharding.constrain(t, "batch", "heads", None, None)
        return sharding.constrain(t, "batch", None, "seq_shard", None)
    if kind == "q":  # q/out (B, S, H, Dh)
        if by_heads:
            return sharding.constrain(t, "batch", None, "heads", None)
        return sharding.constrain(t, "batch", "seq_shard", None, None)
    if kind == "kv":  # expanded k/v (B, T, H, Dh)
        if by_heads:
            return sharding.constrain(t, "batch", None, "heads", None)
        return sharding.constrain(t, "batch", None, None, None)
    return t


def apply_attention(
    p,
    cfg: ModelConfig,
    x: jax.Array,            # (B, S, D)
    positions: jax.Array,    # (B, S)
    *,
    local: bool,
    kv: tuple[jax.Array, jax.Array] | None = None,     # override K/V source
    kv_positions: jax.Array | None = None,             # (B, T)
    kv_mask: jax.Array | None = None,                  # (B, T) extra validity
) -> jax.Array:
    """Causal (optionally windowed) GQA attention.

    Training/prefill: ``kv`` is None — K/V come from ``x``.
    Decode: caller passes the cache as ``kv`` (+ positions/mask), ``x`` is
    the single-step query.
    """
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G = H // Hkv
    window = cfg.window if local else None

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        kv_positions = positions
    else:
        k, v = kv  # (B, T, Hkv, Dh) — already projected + roped by caller

    if cfg.qk_norm:
        q = _qk_normalize(q, p["q_norm"], cfg.norm_eps)
        if kv is None:
            k = _qk_normalize(k, p["k_norm"], cfg.norm_eps)

    q = apply_rope(q, positions, cfg.rope_theta)
    if kv is None:
        k = apply_rope(k, kv_positions, cfg.rope_theta)
        q = _constrain_attn(q, H, "q")
    else:
        # decode: the cache is TIME-sharded; q must be replicated over the
        # model axis or GSPMD reshards the whole cache stack to heads
        # (observed as a hoisted 4.3 GB fp32 copy).
        q = sharding.constrain(q, "batch", None, None, None)

    # Expand K/V to the full H query heads so the score tensor shards on a
    # divisible axis (kv cache stays at Hkv — expansion is a cheap
    # broadcast XLA fuses into the einsum).
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    if kv is None:
        k = _constrain_attn(k, H, "kv")
        v = _constrain_attn(v, H, "kv")
    else:
        k = sharding.constrain(k, "batch", "cache_seq", None, None)
        v = sharding.constrain(v, "batch", "cache_seq", None, None)

    scale = 1.0 / math.sqrt(Dh)
    decode_mode = kv is not None

    def attn_core(q_blk, qpos_blk):
        """Scores+softmax+V for one query block. q_blk: (B, c, H, Dh).

        Operands stay bf16 with fp32 accumulation (MXU semantics):
        converting k/v to fp32 would make XLA hoist an fp32 copy of the
        ENTIRE stacked KV cache out of the layer scan (4.3 GB/chip for
        grok's 32k cache — observed before this fix).
        """
        s = jnp.einsum(
            "bqhk,bthk->bhqt", q_blk * jnp.asarray(scale, q_blk.dtype), k,
            preferred_element_type=jnp.float32)
        if decode_mode:
            # cache (and thus scores) are TIME-sharded over TP; the V
            # contraction psums a tiny (B,1,H,Dh) — context parallelism.
            s = sharding.constrain(s, "batch", None, None, "cache_seq")
        else:
            s = _constrain_attn(s, H, "scores")
        if cfg.attn_softcap is not None:
            s = jnp.tanh(s / cfg.attn_softcap) * cfg.attn_softcap
        qp = qpos_blk[:, None, :, None]             # (B,1,c,1)
        kp = kv_positions[:, None, None, :]         # (B,1,1,T)
        m = kp <= qp
        if window is not None:
            m = jnp.logical_and(m, kp > qp - window)
        if kv_mask is not None:
            m = jnp.logical_and(m, kv_mask[:, None, None, :])
        s = jnp.where(m, s, -1e30)
        probs = jax.nn.softmax(s, axis=-1)          # fp32 softmax
        o = jnp.einsum("bhqt,bthk->bqhk", probs.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.astype(x.dtype)

    nc = cfg.attn_q_chunks
    if nc > 1 and S % nc == 0 and S > nc:
        # Scan over query blocks: the S x T score tensor never exists —
        # only one (B, H, S/nc, T) block at a time (flash principle at the
        # XLA-graph level; the Pallas kernel is the TPU in-VMEM version).
        c = S // nc
        q_blocks = jnp.moveaxis(q.reshape(B, nc, c, H, Dh), 1, 0)
        pos_blocks = jnp.moveaxis(positions.reshape(B, nc, c), 1, 0)

        def step(_, inp):
            qb, pb = inp
            return None, attn_core(qb, pb)

        _, out_blocks = jax.lax.scan(step, None, (q_blocks, pos_blocks))
        out = jnp.moveaxis(out_blocks, 0, 1).reshape(B, S, H, Dh)
    else:
        out = attn_core(q, positions)

    if decode_mode:
        out = sharding.constrain(out, "batch", None, None, None)
    else:
        out = _constrain_attn(out, H, "q")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return sharding.constrain(y, "batch", None, "embed")


def project_kv(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """K/V projection (+rope, +k-norm) for cache fill during decode."""
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        k = _qk_normalize(k, p["k_norm"], cfg.norm_eps)
    k = apply_rope(k, positions, cfg.rope_theta)
    return k, v
