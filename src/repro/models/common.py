"""Shared model building blocks: norms, activations, RoPE, initializers.

Pure-JAX (no flax): params are nested dicts of jnp arrays; every module is an
``init(key, ...) -> params`` plus an ``apply(params, x, ...) -> y`` pair.
bf16 weights/activations by default, fp32 for norm statistics and softmax.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.obs.trace import scope

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype=DTYPE) -> jnp.ndarray:
    """Truncated-normal fan-in init (MaxText-style)."""
    std = 1.0 / math.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (in_dim, out_dim),
                                        jnp.float32) * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype=DTYPE) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str = "rmsnorm"):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


@scope("norm")
def norm_apply(params, x: jnp.ndarray, kind: str = "rmsnorm",
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * params["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, ff: int, kind: str = "swiglu", dtype=DTYPE):
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {"wi": dense_init(ks[0], d, ff, dtype),
                "wg": dense_init(ks[1], d, ff, dtype),
                "wo": dense_init(ks[2], ff, d, dtype)}
    return {"wi": dense_init(ks[0], d, ff, dtype),
            "wo": dense_init(ks[2], ff, d, dtype)}


@scope("mlp")
def mlp_apply(params, x: jnp.ndarray, kind: str = "swiglu") -> jnp.ndarray:
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])
    else:
        h = jax.nn.gelu(x @ params["wi"])
    return h @ params["wo"]


# ---------------------------------------------------------------------------
# Positional embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                               # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                          # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pos(seq: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def softcap(x: jnp.ndarray, cap: Optional[float]) -> jnp.ndarray:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap
