"""GQA attention with RoPE / M-RoPE, qk-norm, sliding window, and KV caches.

Three entry modes:
  * ``full``    — whole-sequence attention (training / encoder).
  * ``prefill`` — whole-sequence attention that also materializes the KV
                  cache (padded to ``cache_len``).
  * ``decode``  — one new token per sequence against a cache, with
                  per-sequence write positions.  Sliding-window archs use a
                  ring-buffer cache of size ``window`` (absolute positions
                  are stored alongside K/V so masking stays exact).

The einsum math here is also the oracle for the Pallas kernels in
``repro.kernels`` (see kernels/ref.py which re-exports pieces of this file).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rms_norm, rope_angles

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_init(key, cfg, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def make_mask(q_pos, kv_pos, *, causal: bool, window: int = 0,
              kv_valid=None):
    """Boolean attention mask (..., S_q, S_kv) from position arrays.

    q_pos: (B, S_q) int32 absolute positions of queries.
    kv_pos: (B, S_kv) int32 absolute positions of keys (-1 => empty slot).
    window: sliding window size (0 = unlimited).
    """
    q = q_pos[:, :, None]
    k = kv_pos[:, None, :]
    mask = k >= 0
    if causal:
        mask &= k <= q
    if window:
        mask &= k > q - window
    if kv_valid is not None:
        mask &= kv_valid[:, None, :]
    return mask


# sequences at or above this length use the blocked (flash-style) path in
# attn_full/attn_prefill; below it the dense einsum path is used (cheaper
# at small scale and the oracle the blocked path is tested against).
BLOCKED_ATTN_THRESHOLD = 2048
BLOCK_Q = 512
BLOCK_K = 1024


def masked_attention(q, k, v, mask, *, scale: float):
    """Reference attention.  q (B,S,H,hd), k/v (B,C,K,hd), mask (B,S,C)."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgh,bckh->bkgsc", qg, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsc,bckh->bskgh", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def blocked_attention(q, k, v, q_pos, kv_pos, *, scale: float, causal: bool,
                      window: int = 0, block_q: int = BLOCK_Q,
                      block_k: int = BLOCK_K):
    """Flash-style attention in pure JAX: scan over KV blocks with online
    softmax, vmapped over query blocks.  Never materializes (S_q, S_kv)
    scores — peak extra memory is O(block_q * block_k) per (B, K, G).

    q (B,S,H,hd); k/v (B,C,K,hd); q_pos (B,S); kv_pos (B,C) (-1 = empty).
    This is the TPU-shaped formulation the Pallas flash_prefill kernel
    implements natively; XLA compiles this version for the dry-run.
    """
    B, S, H, hd = q.shape
    C, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, C)
    assert S % bq == 0 and C % bk == 0, (S, bq, C, bk)
    nq, nk = S // bq, C // bk

    qb = q.reshape(B, nq, bq, K, G, hd).astype(jnp.float32)
    qpb = q_pos.reshape(B, nq, bq)
    kb = k.reshape(B, nk, bk, K, hd)
    vb = v.reshape(B, nk, bk, K, hd)
    kpb = kv_pos.reshape(B, nk, bk)

    def q_block(qi, qp):
        """qi (B,bq,K,G,hd), qp (B,bq) -> (B,bq,K,G,hd)."""

        def kv_step(carry, inp):
            m_prev, l_prev, acc = carry
            ki, vi, kp = inp                       # (B,bk,K,hd),(B,bk)
            s = jnp.einsum("bqkgh,bckh->bkgqc", qi,
                           ki.astype(jnp.float32)) * scale
            ok = kp[:, None, :] >= 0
            if causal:
                ok &= kp[:, None, :] <= qp[:, :, None]
            if window:
                ok &= kp[:, None, :] > qp[:, :, None] - window
            s = jnp.where(ok[:, None, None], s, NEG_INF)
            m_cur = jnp.max(s, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(ok[:, None, None], p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p, vi.astype(jnp.float32))
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, K, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, bq), jnp.float32)
        a0 = jnp.zeros((B, K, G, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpb.swapaxes(0, 1)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,K,G,bq,hd)
        return out.transpose(0, 3, 1, 2, 4)            # (B,bq,K,G,hd)

    out = jax.lax.map(lambda args: q_block(*args),
                      (qb.swapaxes(0, 1), qpb.swapaxes(0, 1)))
    out = out.swapaxes(0, 1).reshape(B, S, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _project_qkv(p, x, cfg, positions):
    """Project + rope.  positions: (B,S) or (3,B,S) for M-RoPE."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (x @ p["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (x @ p["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if cfg.n_heads > 0:
        ang = rope_angles(positions, hd, cfg.rope_theta, cfg.mrope_sections)
        q = apply_rope(q, ang)
        k = apply_rope(k, ang)
    return q, k, v


def attn_full(p, x, cfg, positions, *, window_override: Optional[int] = None):
    """Whole-sequence attention (train / encoder).  Returns y (B,S,d)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    pos2d = positions if positions.ndim == 2 else positions[0]
    window = cfg.sliding_window if window_override is None else window_override
    if S >= BLOCKED_ATTN_THRESHOLD:
        y = blocked_attention(q, k, v, pos2d, pos2d, causal=cfg.causal,
                              window=window, scale=cfg.head_dim ** -0.5)
    else:
        mask = make_mask(pos2d, pos2d, causal=cfg.causal, window=window)
        y = masked_attention(q, k, v, mask, scale=cfg.head_dim ** -0.5)
    return y.reshape(B, S, -1) @ p["wo"]


def init_kv_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16,
                  quant: bool = False):
    """Empty cache.  For SWA archs callers may pass cache_len=window.

    quant=True (beyond-paper §Perf): K/V stored as symmetric per-token
    per-head int8 with fp scales (KVQuant-style).  Decode is KV-streaming
    bound at long contexts; int8 halves those bytes.  Dequant happens at
    the attention consumer (fused on TPU).
    """
    shp = (batch, cache_len, cfg.n_kv_heads, cfg.head_dim)
    pos = jnp.full((batch, cache_len), -1, jnp.int32)
    if quant:
        sshp = shp[:-1] + (1,)
        return {
            "k": {"q": jnp.zeros(shp, jnp.int8),
                  "s": jnp.zeros(sshp, jnp.float32)},
            "v": {"q": jnp.zeros(shp, jnp.int8),
                  "s": jnp.zeros(sshp, jnp.float32)},
            "pos": pos,
        }
    return {
        "k": jnp.zeros(shp, dtype),
        "v": jnp.zeros(shp, dtype),
        "pos": pos,
    }


def _kv_quantize(x):
    """x (..., hd) -> (int8 q, fp32 s) with s shaped (..., 1)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127
                 ).astype(jnp.int8)
    return q, s


def _kv_resolve(c, dtype=jnp.float32):
    """Cache leaf -> dense array (dequantize if int8)."""
    if isinstance(c, dict):
        return c["q"].astype(dtype) * c["s"].astype(dtype)
    return c


def attn_prefill(p, x, cfg, positions, cache_len: int, cache_dtype=jnp.bfloat16):
    """Full attention + build a cache of the (possibly windowed) suffix."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    pos2d = positions if positions.ndim == 2 else positions[0]
    if S >= BLOCKED_ATTN_THRESHOLD:
        y = blocked_attention(q, k, v, pos2d, pos2d, causal=cfg.causal,
                              window=cfg.sliding_window,
                              scale=cfg.head_dim ** -0.5)
    else:
        mask = make_mask(pos2d, pos2d, causal=cfg.causal,
                         window=cfg.sliding_window)
        y = masked_attention(q, k, v, mask, scale=cfg.head_dim ** -0.5)
    y = y.reshape(B, S, -1) @ p["wo"]
    return y, prefill_cache(cfg, k, v, pos2d, cache_len, cache_dtype)


def prefill_cache(cfg, k, v, pos2d, cache_len: int, cache_dtype=jnp.bfloat16):
    """A cache of the (possibly windowed) suffix of k/v (B,S,K,hd) at
    positions ``pos2d`` (B,S)."""
    B, S = pos2d.shape
    cache = init_kv_cache(cfg, B, cache_len, cache_dtype)
    if cfg.sliding_window and cache_len <= cfg.sliding_window:
        # Ring buffer: keep the last `cache_len` tokens at slot pos % len.
        # Written via a one-hot contraction instead of scatter: scatter
        # along a sharded cache axis forces SPMD to replicate the cache
        # ("involuntary full rematerialization"); the one-hot einsum is an
        # MXU matmul that partitions cleanly.
        take = min(S, cache_len)
        ks, vs, ps = k[:, -take:], v[:, -take:], pos2d[:, -take:]
        slots = ps % cache_len                           # (B, take)
        oh = (slots[:, :, None]
              == jnp.arange(cache_len)[None, None, :])   # (B, take, C)
        ohf = oh.astype(cache_dtype)
        cache["k"] = jnp.einsum("bsc,bskh->bckh", ohf,
                                ks.astype(cache_dtype))
        cache["v"] = jnp.einsum("bsc,bskh->bckh", ohf,
                                vs.astype(cache_dtype))
        written = oh.any(axis=1)                          # (B, C)
        pos_val = jnp.einsum("bsc,bs->bc", oh.astype(jnp.float32),
                             ps.astype(jnp.float32)).astype(jnp.int32)
        cache["pos"] = jnp.where(written, pos_val, cache["pos"])
    else:
        take = min(S, cache_len)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k[:, :take].astype(cache_dtype), 0, axis=1)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v[:, :take].astype(cache_dtype), 0, axis=1)
        cache["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], pos2d[:, :take], 0, axis=1)
    return cache


def attn_decode(p, x, cfg, cache, write_pos):
    """One-token decode.  x (B,1,d); write_pos (B,) absolute position.

    Returns (y (B,1,d), updated cache).  Works for both linear caches and
    ring-buffer (SWA) caches — the slot is ``pos % cache_len`` when the
    cache is windowed, else ``pos``.
    """
    B = x.shape[0]
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(write_pos[None, :, None], (3, B, 1))
    else:
        positions = write_pos[:, None]
    q, k, v = _project_qkv(p, x, cfg, positions)
    y, cache = decode_cache_attend(cfg, q, k, v, cache, write_pos,
                                   scale=cfg.head_dim ** -0.5)
    return y.reshape(B, 1, -1) @ p["wo"], cache


def decode_cache_attend(cfg, q, k, v, cache, write_pos, *, scale: float):
    """Write one token's k/v (B,1,K,hd) into ``cache`` at ``write_pos``
    and attend q (B,1,H,hd) over it.  Returns (y (B,1,H,hd), cache)."""
    quantized = isinstance(cache["k"], dict)
    C = (cache["k"]["q"] if quantized else cache["k"]).shape[1]
    windowed = bool(cfg.sliding_window) and C <= cfg.sliding_window
    slots = (write_pos % C) if windowed else write_pos
    # one-hot write (see attn_prefill): scatter along the sharded cache
    # axis would force SPMD to replicate the cache.
    oh = slots[:, None] == jnp.arange(C)[None, :]         # (B, C)

    def write(leaf, new):
        """Insert new (B, K, hd) into leaf at the one-hot slot."""
        if isinstance(leaf, dict):
            nq, ns = _kv_quantize(new)
            return {"q": jnp.where(oh[:, :, None, None], nq[:, None],
                                   leaf["q"]),
                    "s": jnp.where(oh[:, :, None, None], ns[:, None],
                                   leaf["s"])}
        return jnp.where(oh[:, :, None, None],
                         new[:, None].astype(leaf.dtype), leaf)

    kc = write(cache["k"], k[:, 0])
    vc = write(cache["v"], v[:, 0])
    pc = jnp.where(oh, write_pos[:, None], cache["pos"])

    mask = make_mask(write_pos[:, None], pc, causal=cfg.causal,
                     window=cfg.sliding_window)
    y = masked_attention(q, _kv_resolve(kc, q.dtype),
                         _kv_resolve(vc, q.dtype), mask, scale=scale)
    return y, {"k": kc, "v": vc, "pos": pc}
