"""Shared neural-net building blocks (pure-functional, dict params).

Every layer is an (init, apply) pair over plain pytrees so the whole
framework stays framework-free (no flax/haiku dependency) and trivially
shardable with pjit: params are dicts of jnp arrays whose tree paths are
matched against sharding rules in repro/distributed/sharding.py.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16,
               scale: float | None = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return {"w": _normal(key, (d_in, d_out), scale, dtype)}


def dense(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    # Accumulation dtype matches activations: the MXU still accumulates
    # fp32 per-tile, but cross-shard partial sums (TP contractions) then
    # travel as bf16 — §Perf iteration 3 halved activation-collective
    # wire bytes this way. fp32 activations keep fp32 end-to-end.
    return jnp.einsum("...i,io->...o", x, params["w"],
                      preferred_element_type=x.dtype)


def embedding_init(key, vocab: int, d_model: int, dtype=jnp.bfloat16) -> Params:
    return {"emb": _normal(key, (vocab, d_model), 1.0, dtype)}


def embed(params: Params, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["emb"], ids, axis=0)


def unembed(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """Tied read-out: logits = x @ embᵀ."""
    return jnp.einsum("...d,vd->...v", x, params["emb"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1 + scale)


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(x.dtype)


def make_norm(kind: str, rms_eps: float = 1e-6):
    if kind == "rms":
        return rmsnorm_init, lambda p, x: rmsnorm(p, x, rms_eps)
    if kind == "ln":
        return layernorm_init, layernorm
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def mlp_init(key, d_model: int, d_ff: int, *, gated: bool = True,
             dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {
        "up": dense_init(k1, d_model, d_ff, dtype),
        "down": dense_init(k2, d_ff, d_model, dtype),
    }
    if gated:
        p["gate"] = dense_init(k3, d_model, d_ff, dtype)
    return p


def mlp(params: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    f = _ACTS[act]
    h = dense(params["up"], x)
    if "gate" in params:
        h = h * f(dense(params["gate"], x))
    else:
        h = f(h)
    return dense(params["down"], h)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., N, d_head) with d_head even; positions: (N,) or (..., N)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                     # (d/2,)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., N, d/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def learned_pos_init(key, max_len: int, d_model: int,
                     dtype=jnp.bfloat16) -> Params:
    return {"pos": _normal(key, (max_len, d_model), 0.02, dtype)}


def add_learned_pos(params: Params, x: jnp.ndarray,
                    positions: jnp.ndarray) -> jnp.ndarray:
    return x + jnp.take(params["pos"], positions, axis=0).astype(x.dtype)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    return cap * jnp.tanh(x / cap)
