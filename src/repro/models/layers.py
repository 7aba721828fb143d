"""Core layers shared by every architecture: norms, RoPE/M-RoPE, MLPs, inits.

Pure functional style: params are nested dicts of jnp arrays; every layer is
``apply(params, x, ...) -> y``.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float = 1.0):
    std = scale / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out)) * std).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(w, x, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def rms_norm_gated(w, x, z, eps: float = 1e-5, n_groups: int = 1):
    """Mamba2-style: RMSNorm(x * silu(z)), the mean square taken over
    each of ``n_groups`` equal slices of the last axis."""
    y = x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype)
    if n_groups == 1:
        return rms_norm(w, y, eps)
    shp = y.shape
    g = y.reshape(shp[:-1] + (n_groups, shp[-1] // n_groups))
    g = rms_norm(jnp.ones((), jnp.float32), g, eps)
    return (g.reshape(shp).astype(jnp.float32)
            * w.astype(jnp.float32)).astype(x.dtype)


def group_norm_heads(w, b, x, n_heads: int, eps: float = 1e-5):
    """RWKV-style per-head group norm.  x: (..., H*hd)."""
    dt = x.dtype
    shp = x.shape
    x = x.reshape(shp[:-1] + (n_heads, shp[-1] // n_heads)).astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    x = x.reshape(shp)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def rope_angles(positions, head_dim: int, theta: float,
                mrope_sections: Tuple[int, ...] = ()):
    """Rotation angles for the given positions.

    positions: (..., S) int32 for ordinary RoPE, or (3, ..., S) for M-RoPE
    (temporal/height/width position streams; Qwen2-VL).  Returns
    (..., S, head_dim//2) float32 angles.
    """
    inv = rope_freqs(head_dim, theta)  # (hd/2,)
    if not mrope_sections:
        return positions.astype(jnp.float32)[..., None] * inv
    # M-RoPE: split the hd/2 frequency channels into (t, h, w) sections and
    # drive each section with its own position stream.
    assert positions.shape[0] == 3, "M-RoPE needs (3, ..., S) positions"
    ang = positions.astype(jnp.float32)[..., None] * inv  # (3, ..., S, hd/2)
    secs = mrope_sections
    assert sum(secs) == inv.shape[0], (secs, inv.shape)
    parts, off = [], 0
    for i, s in enumerate(secs):
        parts.append(ang[i, ..., off:off + s])
        off += s
    return jnp.concatenate(parts, axis=-1)  # (..., S, hd/2)


def apply_rope(x, angles):
    """x: (..., S, H, hd); angles: (..., S, hd/2) -> rotated x (same dtype)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, act: str, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype)}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp_apply(p, x, act: str):
    h = x @ p["w_up"]
    if act == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * h
    else:
        h = jax.nn.gelu(h)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# Cross-entropy (vocab-shardable)
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits, labels, mask=None):
    """Mean CE over valid tokens.  logits (..., V) any float dtype."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
