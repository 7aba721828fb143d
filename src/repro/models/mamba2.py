"""Mamba2 (SSD) mixer — chunked scan for train/prefill, O(1) state decode.

TPU adaptation: the reference GPU implementation fuses the chunked SSD
algorithm in Triton.  Here the chunk loop is a ``lax.scan`` whose body
holds only one chunk's quadratic term (B, H, Q, Q) — the working set stays
small and the intra-chunk einsums are MXU-shaped matmuls, which is the
TPU-native formulation (quadratic-within-chunk, recurrent-across-chunk).

State carried between chunks / decode steps:
  h    : (B, H, hd, ds)   SSD state
  conv : (B, d_conv-1, d_xbc) depthwise-conv tail

Layout: xBC = [x (d_in) | B (G x ds) | C (G x ds)] with G = ``n_groups``;
head n reads the B/C of group n // (H / G) (Zamba2: G = 2), and the
gated RMSNorm takes its mean square over each of G slices of d_in.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import dense_init, rms_norm_gated


def _dims(cfg):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    d_xbc = d_in + 2 * s.n_groups * s.d_state
    return d_in, n_heads, d_xbc


def _split_xbc(xBC, cfg):
    """(..., d_xbc) -> x (..., d_in), B and C (..., G, ds)."""
    s = cfg.ssm
    d_in, _, _ = _dims(cfg)
    gs = s.n_groups * s.d_state
    lead = xBC.shape[:-1]
    Bm = xBC[..., d_in:d_in + gs].reshape(lead + (s.n_groups, s.d_state))
    Cm = xBC[..., d_in + gs:].reshape(lead + (s.n_groups, s.d_state))
    return xBC[..., :d_in], Bm, Cm


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def mamba_init(key, cfg, dtype=jnp.float32):
    s = cfg.ssm
    d = cfg.d_model
    d_in, H, d_xbc = _dims(cfg)
    ks = jax.random.split(key, 6)
    return {
        # Three separate input projections instead of one fused
        # [z|xBC|dt] matrix: identical FLOPs, but the fused variant's
        # *slice* VJPs each pad their gradient back to the full
        # (B, S, 2*d_in+2*ds+H) width — several multi-GB f32 buffers per
        # layer in the train step (§Perf, zamba2 iteration 1).
        "z_proj": dense_init(ks[0], d, d_in, dtype),
        "xbc_proj": dense_init(ks[4], d, d_xbc, dtype),
        "dt_proj": dense_init(ks[5], d, H, dtype),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, d_xbc)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((d_xbc,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 8.0, H)).astype(jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm_w": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(ks[3], d_in, d, dtype),
    }


def init_mamba_state(cfg, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    d_in, H, d_xbc = _dims(cfg)
    return {
        "h": jnp.zeros((batch, H, s.head_dim, s.d_state), dtype),
        "conv": jnp.zeros((batch, s.d_conv - 1, d_xbc), dtype),
    }


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------

def _split_proj(p, x, cfg):
    """x (B,T,d) -> z (B,T,d_in), xBC (B,T,d_xbc), dt (B,T,H) (pre-softplus)."""
    z = x @ p["z_proj"].astype(x.dtype)
    xBC = x @ p["xbc_proj"].astype(x.dtype)
    dt = x @ p["dt_proj"].astype(x.dtype)
    return z, xBC, dt


def _conv_full(p, xBC, conv_state):
    """Causal depthwise conv along T.  conv_state: (B, d_conv-1, d_xbc)."""
    w = p["conv_w"].astype(xBC.dtype)                   # (K, C)
    K = w.shape[0]
    ext = jnp.concatenate([conv_state.astype(xBC.dtype), xBC], axis=1)
    out = sum(ext[:, i:i + xBC.shape[1]] * w[i] for i in range(K))
    out = out + p["conv_b"].astype(xBC.dtype)
    new_state = ext[:, -(K - 1):] if K > 1 else conv_state
    return jax.nn.silu(out), new_state


def _conv_step(p, xBC_t, conv_state):
    """One-token conv.  xBC_t: (B, C)."""
    w = p["conv_w"].astype(xBC_t.dtype)
    ext = jnp.concatenate([conv_state.astype(xBC_t.dtype),
                           xBC_t[:, None]], axis=1)     # (B, K, C)
    out = (ext * w[None]).sum(axis=1) + p["conv_b"].astype(xBC_t.dtype)
    return jax.nn.silu(out), ext[:, 1:]


# ---------------------------------------------------------------------------
# Chunked SSD scan (train / prefill)
# ---------------------------------------------------------------------------

def _ssd_chunk(carry_h, inp, *, hd: int, ds: int):
    """One chunk, heads split into their B/C groups.  carry_h:
    (B,G,Hg,hd,ds) fp32.

    inp: xh (B,Q,G,Hg,hd), Bm/Cm (B,Q,G,ds), dA (B,Q,G,Hg) [negative
         log-decay*dt], dt (B,Q,G,Hg).
    """
    xh, Bm, Cm, dA, dt = inp
    xdt = (xh * dt[..., None]).astype(jnp.float32)      # (B,Q,G,Hg,hd)
    cum = jnp.cumsum(dA, axis=1)                        # (B,Q,G,Hg) (<= 0)
    Q = xh.shape[1]
    Cf, Bf = Cm.astype(jnp.float32), Bm.astype(jnp.float32)
    # --- intra-chunk quadratic term -----------------------------------
    scores = jnp.einsum("bqgn,btgn->bqtg", Cf, Bf)      # (B,Q,Q,G)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]
    # mask the exponent BEFORE exp: for t > q the argument is positive and
    # can overflow, and grad-of-where(inf) poisons the backward pass
    delta = cum[:, :, None] - cum[:, None, :]           # (B,Q,T,G,Hg)
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, delta, 0.0)), 0.0)
    y_intra = jnp.einsum("bqtg,bqtgh,btghp->bqghp", scores, decay, xdt)
    # --- inter-chunk (state from previous chunks) ----------------------
    y_inter = jnp.einsum("bqgn,bghpn,bqgh->bqghp", Cf, carry_h,
                         jnp.exp(cum))
    # --- state update ---------------------------------------------------
    decay_to_end = jnp.exp(cum[:, -1:] - cum)           # (B,Q,G,Hg)
    s_new = jnp.einsum("btgh,btghp,btgn->bghpn", decay_to_end, xdt, Bf)
    chunk_decay = jnp.exp(cum[:, -1])[..., None, None]  # (B,G,Hg,1,1)
    h_next = carry_h * chunk_decay + s_new
    return h_next, (y_intra + y_inter)


def mamba_apply_full(p, x, cfg, state=None,
                     lengths=None) -> Tuple[jnp.ndarray, dict]:
    """Full-sequence mixer.  x: (B,T,d).  Returns (y (B,T,d), new state).

    T must be a multiple of cfg.ssm.chunk_size (callers pad).

    ``lengths`` (B,) marks per-row valid prefixes of a right-padded
    batch: positions >= lengths[b] become *identity* steps (dt = 0, so
    no state write and no decay) and the returned state is exactly the
    state after lengths[b] tokens — the conv tail is gathered per row
    instead of sliced from the padded end.  Outputs at padded positions
    are garbage and must be discarded by the caller.  A row with
    lengths[b] == 0 keeps its incoming state untouched.
    """
    s = cfg.ssm
    d_in, H, d_xbc = _dims(cfg)
    hd, ds, G = s.head_dim, s.d_state, s.n_groups
    Hg = H // G
    B, T, _ = x.shape
    if state is None:
        state = init_mamba_state(cfg, B)

    z, xBC_raw, dt_raw = _split_proj(p, x, cfg)
    xBC, conv_new = _conv_full(p, xBC_raw, state["conv"])
    if lengths is not None and s.d_conv > 1:
        # per-row conv tail: the raw (pre-silu) xBC values at positions
        # [len-K+1, len) — ext index len..len+K-2 (identity for len==0)
        K = s.d_conv
        ext = jnp.concatenate([state["conv"].astype(xBC_raw.dtype),
                               xBC_raw], axis=1)
        idx = lengths[:, None] + jnp.arange(K - 1)[None, :]   # (B, K-1)
        conv_new = jnp.take_along_axis(
            ext, idx[..., None], axis=1).astype(state["conv"].dtype)
    xs, Bm, Cm = _split_xbc(xBC, cfg)
    xh = xs.reshape(B, T, G, Hg, hd)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,T,H)
    if lengths is not None:
        valid = jnp.arange(T)[None, :] < lengths[:, None]     # (B, T)
        dt = jnp.where(valid[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"])                            # (H,) negative
    dA = (dt * A).reshape(B, T, G, Hg)                  # <= 0
    dt = dt.reshape(B, T, G, Hg)

    Q = min(s.chunk_size, T)
    pad = (-T) % Q

    def padded(a):
        # identity steps: dt = 0 (no state write), dA = 0 (no decay)
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    Tp = T + pad
    nc = Tp // Q

    def body(h, chunk):
        return _ssd_chunk(h, chunk, hd=hd, ds=ds)

    def chunked(a):
        return padded(a).reshape((B, nc, Q) + a.shape[2:]).swapaxes(0, 1)

    chunks = tuple(chunked(a) for a in (xh, Bm, Cm, dA, dt))
    h0 = state["h"].astype(jnp.float32).reshape(B, G, Hg, hd, ds)
    h_final, ys = jax.lax.scan(body, h0, chunks)
    y = ys.swapaxes(0, 1).reshape(B, Tp, H, hd)[:, :T]  # fp32
    y = y + p["D"][None, None, :, None] \
        * xh.reshape(B, T, H, hd).astype(jnp.float32)
    y = y.reshape(B, T, d_in).astype(x.dtype)
    y = rms_norm_gated(p["norm_w"], y, z, cfg.norm_eps, G)
    out = y @ p["out_proj"].astype(x.dtype)
    return out, {"h": h_final.reshape(B, H, hd, ds), "conv": conv_new}


# ---------------------------------------------------------------------------
# Single-token decode
# ---------------------------------------------------------------------------

def mamba_decode_step(p, x, cfg, state) -> Tuple[jnp.ndarray, dict]:
    """x: (B,1,d) -> (y (B,1,d), new state)."""
    s = cfg.ssm
    d_in, H, d_xbc = _dims(cfg)
    hd, ds, G = s.head_dim, s.d_state, s.n_groups
    B = x.shape[0]
    z, xBC, dt_raw = _split_proj(p, x[:, 0:1], cfg)
    xBC_t, conv_new = _conv_step(p, xBC[:, 0], state["conv"])
    xs, Bm, Cm = _split_xbc(xBC_t, cfg)                 # B/C (B,G,ds)
    xh = xs.reshape(B, G, H // G, hd)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A).reshape(B, G, H // G)
    h = state["h"].astype(jnp.float32).reshape(B, G, H // G, hd, ds)
    xdt = (xh * dt.reshape(B, G, H // G)[..., None]).astype(jnp.float32)
    h_new = h * decay[..., None, None] \
        + jnp.einsum("bghp,bgn->bghpn", xdt, Bm.astype(jnp.float32))
    y = jnp.einsum("bghpn,bgn->bghp", h_new, Cm.astype(jnp.float32))
    y = y.reshape(B, H, hd) \
        + p["D"][None, :, None] * xs.reshape(B, H, hd).astype(jnp.float32)
    y = y.reshape(B, 1, d_in).astype(x.dtype)
    y = rms_norm_gated(p["norm_w"], y, z, cfg.norm_eps, G)
    out = y @ p["out_proj"].astype(x.dtype)
    return out, {"h": h_new.reshape(B, H, hd, ds), "conv": conv_new}


# ---------------------------------------------------------------------------
# Oracle: naive per-token recurrence (tests only)
# ---------------------------------------------------------------------------

def mamba_apply_recurrent(p, x, cfg, state=None):
    """Token-by-token reference for mamba_apply_full."""
    B, T, _ = x.shape
    if state is None:
        state = init_mamba_state(cfg, B)
    ys = []
    for t in range(T):
        y, state = mamba_decode_step(p, x[:, t:t + 1], cfg, state)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), state
