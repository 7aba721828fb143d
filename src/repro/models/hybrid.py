"""The Zamba2 hybrid block (arXiv:2411.15242), shared by the contiguous
model (models/model.py) and the paged engine's runtime
(serving/runtimes.py).

Every layer is a Mamba2 layer.  At the hybrid layers
(``cfg.hybrid_ids()``) one of ``cfg.n_mem_blocks`` shared transformer
blocks, block ``j mod n_mem_blocks`` for the j-th hybrid layer, reads
the hidden state joined to the input embeddings ``x0`` and feeds that
layer's Mamba2 input; it has no residual of its own::

    u = RMSNorm_in([x ; x0])                        # width 2d
    a = Wo . attn(RoPE(Wq u), RoPE(Wk u), Wv u)     # scale (hd/2)^-0.5
    m = RMSNorm_ff(a)
    g, p = split(m Wgu + (m A_j) B_j)                # rank-r adapter
    t = Wlin_j . (Wdown . (act(g) * p))              # act: exact GELU
    x = x + Mamba2(RMSNorm(x + t))

Parameters: ``groups[0]`` stacks every layer's ``ln`` and ``mamba``;
``shared`` stacks the blocks (``ln_in``, ``attn.{wq,wk,wv,wo}``,
``ln_ff``, ``mlp.{w_gate_up,w_down}``); ``hybrid`` stacks each hybrid
layer's ``linear`` and, with ``adapter_rank``, ``adapter_a`` and
``adapter_b``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import apply_rope, dense_init, rms_norm, rope_angles


def init_shared(key, cfg, dtype=jnp.float32):
    """One shared block."""
    a, d, hd = cfg.attn_in_dim, cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 6)
    return {
        "ln_in": jnp.ones((a,), dtype),
        "attn": {"wq": dense_init(ks[0], a, cfg.n_heads * hd, dtype),
                 "wk": dense_init(ks[1], a, cfg.n_kv_heads * hd, dtype),
                 "wv": dense_init(ks[2], a, cfg.n_kv_heads * hd, dtype),
                 "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype)},
        "ln_ff": jnp.ones((d,), dtype),
        "mlp": {"w_gate_up": dense_init(ks[4], d, 2 * cfg.d_ff, dtype),
                "w_down": dense_init(ks[5], cfg.d_ff, d, dtype)},
    }


def init_hybrid_layer(key, cfg, dtype=jnp.float32):
    """What one hybrid layer adds to its shared block."""
    d, r = cfg.d_model, cfg.adapter_rank
    ks = jax.random.split(key, 3)
    p = {"linear": dense_init(ks[0], d, d, dtype)}
    if r:
        p["adapter_a"] = dense_init(ks[1], d, r, dtype)
        p["adapter_b"] = dense_init(ks[2], r, 2 * cfg.d_ff, dtype)
    return p


def block_params(params, cfg, j: int):
    """(shared block, hybrid-layer params) of the j-th hybrid layer."""
    b = j % cfg.n_mem_blocks
    return (jax.tree.map(lambda a: a[b], params["shared"]),
            jax.tree.map(lambda a: a[j], params["hybrid"]))


def block_qkv(blk, x, x0, cfg, positions):
    """Norm of ``[x ; x0]`` and RoPE'd q, k, v (B, T, heads, hd).
    ``positions`` (B, T)."""
    B, T, _ = x.shape
    hd = cfg.head_dim
    u = rms_norm(blk["ln_in"], jnp.concatenate([x, x0.astype(x.dtype)], -1),
                 cfg.norm_eps)
    ap = blk["attn"]
    q = (u @ ap["wq"].astype(u.dtype)).reshape(B, T, cfg.n_heads, hd)
    k = (u @ ap["wk"].astype(u.dtype)).reshape(B, T, cfg.n_kv_heads, hd)
    v = (u @ ap["wv"].astype(u.dtype)).reshape(B, T, cfg.n_kv_heads, hd)
    ang = rope_angles(positions, hd, cfg.rope_theta)
    return apply_rope(q, ang), apply_rope(k, ang), v


def block_out(blk, hl, y, cfg):
    """Attention output ``y`` (B, T, heads * hd) -> the injection ``t``
    (B, T, d) into the hybrid layer's Mamba2 input."""
    dt = y.dtype
    a = y @ blk["attn"]["wo"].astype(dt)
    m = rms_norm(blk["ln_ff"], a, cfg.norm_eps)
    gu = m @ blk["mlp"]["w_gate_up"].astype(dt)
    if "adapter_a" in hl:
        gu = gu + (m @ hl["adapter_a"].astype(dt)) @ hl["adapter_b"].astype(dt)
    g, p = jnp.split(gu, 2, axis=-1)
    act = jax.nn.gelu(g, approximate=False) if cfg.act == "gelu" \
        else jax.nn.silu(g)
    t = (act * p) @ blk["mlp"]["w_down"].astype(dt)
    return t @ hl["linear"].astype(dt)


# Bytes of one slice's widest activation, the MLP's gate and up
# projections (rows, tokens, 2 d_ff) in float32, in a full-sequence
# pass over many rows (PRM scoring).
ROW_SLICE_BYTES = 512 << 20


def row_slice(cfg, rows: int, tokens: int) -> int:
    """Rows per slice of a full-sequence pass: ``rows``, halved while
    a slice's widest activation is over ``ROW_SLICE_BYTES``."""
    n = rows
    while n > 1 and n % 2 == 0 and \
            4 * n * tokens * 2 * cfg.d_ff > ROW_SLICE_BYTES:
        n //= 2
    return n


def run_layers(cfg, x, mamba, block):
    """Walk the layers in order.  ``mamba(x, a, b, t)`` runs Mamba2
    layers ``a .. b-1`` and returns ``x`` (``t``, where given, joins the
    input of the one layer ``a``); ``block(j, x)`` is the j-th hybrid
    layer's injection."""
    i = 0
    for j, h in enumerate(cfg.hybrid_ids()):
        if h > i:
            x = mamba(x, i, h, None)
        x = mamba(x, h, h + 1, block(j, x))
        i = h + 1
    if i < cfg.n_layers:
        x = mamba(x, i, cfg.n_layers, None)
    return x


def mamba_layer(cfg, mixer, t):
    """Scan body ``(x, (layer params, state)) -> (x, new state)`` of one
    Mamba2 layer; ``mixer(mamba_params, h, state) -> (y, new state)``."""
    def body(x, blk_st):
        blk, st = blk_st
        h = x if t is None else x + t
        y, new = mixer(blk["mamba"], rms_norm(blk["ln"], h, cfg.norm_eps), st)
        return x + y, new
    return body
