"""Plain reference of the served models, in float32 at full matmul
precision, with no kernels, cache, paging or batching.

It imports nothing of the program.  Weights come as the benchmark made
them (``weights.py``): a dict with ``embed`` (V, d), ``groups`` (one
group of layer-stacked blocks: ``ln1``, ``attn.{wq,wk,wv,wo}``,
``ln2``, ``mlp.{w_up,w_down[,w_gate]}``), ``ln_f`` and ``lm_head`` or
``value_head``.  The layer equations, per configuration file:

    h = x + Wo . attn(RoPE(Wq . n1), RoPE(Wk . n1), Wv . n1),  n1 = RMSNorm(x)
    y = h + Wdown . (silu(Wgate . n2) * (Wup . n2)),          n2 = RMSNorm(h)

(GELU, tanh form, in place of the gated unit for the embedder), causal
attention with grouped key/value heads for the generator and the PRM,
bidirectional attention for the embedder.  RoPE rotates the two halves
of each head.

``fp8=True`` is the correctness control: every matrix product takes
both operands rounded to float8 e4m3 (a scale per weight matrix and
per activation row, accumulation in float32), the next precision below
the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q8(x, axis=None):
    """Round to float8 e4m3 under a scale per ``axis`` slice (whole
    tensor when None)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, fp8):
    if fp8:
        x, w = _q8(x, axis=-1), _q8(w)
    return jnp.matmul(x, w, precision=HI)


def _ein(spec, a, b, fp8, b_axis=-1):
    """``a`` is scaled along its last (contracted) axis, ``b`` along
    ``b_axis``, its contracted one."""
    if fp8:
        a, b = _q8(a, axis=-1), _q8(b, axis=b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(w, x, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _trunk(params, c, tokens, mask, fp8):
    """Final-norm hidden states (T, d) of one sequence; ``mask`` (T, T)
    says which keys each query sees."""
    H = c["num_attention_heads"]
    K = c.get("num_key_value_heads", H)
    d = c["hidden_size"]
    hd = c.get("head_dim", d // H)
    eps = c.get("rms_norm_eps", 1e-5)
    theta = c.get("rope_theta", 10000.0)
    T = tokens.shape[0]
    pos = jnp.arange(T)
    emb = _q8(params["embed"]) if fp8 else params["embed"]
    x = emb[tokens].astype(jnp.float32)
    g = params["groups"][0]
    for l in range(c["num_hidden_layers"]):
        blk = jax.tree.map(lambda a: a[l], g)
        a = blk["attn"]
        n1 = _rms(blk["ln1"], x, eps)
        q = _rope(_mm(n1, a["wq"], fp8).reshape(T, H, hd), pos, theta)
        k = _rope(_mm(n1, a["wk"], fp8).reshape(T, K, hd), pos, theta)
        v = _mm(n1, a["wv"], fp8).reshape(T, K, hd)
        q = q.reshape(T, K, H // K, hd)
        s = _ein("tkgh,ckh->kgtc", q, k, fp8) * hd ** -0.5
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _ein("kgtc,ckh->tkgh", p, v, fp8, b_axis=0)
        x = x + _mm(o.reshape(T, H * hd), a["wo"], fp8)
        n2 = _rms(blk["ln2"], x, eps)
        m = blk["mlp"]
        if "w_gate" in m:
            u = jax.nn.silu(_mm(n2, m["w_gate"], fp8)) * _mm(n2, m["w_up"],
                                                             fp8)
        else:
            u = jax.nn.gelu(_mm(n2, m["w_up"], fp8))
        x = x + _mm(u, m["w_down"], fp8)
    return _rms(params["ln_f"], x, eps)


def _causal(T):
    return jnp.tril(jnp.ones((T, T), bool))


def _cfg_key(c):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _token_fn(ckey, temperature, fp8):
    c = dict(ckey)

    def fn(params, tokens, at, keys):
        """Scores of the tokens at positions ``at``: logits from
        positions ``at - 1``, over the temperature, plus the sampling
        noise that ``keys`` draw."""
        h = _trunk(params, c, tokens, _causal(tokens.shape[0]), fp8)
        lg = _mm(h[at - 1], params["lm_head"], fp8)
        if temperature > 0:
            noise = jax.vmap(lambda k: jax.random.gumbel(
                k, (lg.shape[-1],), jnp.float32))(keys)
            lg = lg / temperature + noise
        return lg

    return jax.jit(fn)


def token_logits(params, c, tokens, at, keys, temperature, fp8=False):
    """Noisy next-token scores (n, V) at the positions ``at`` of one
    padded sequence, ``keys`` being each position's sampling key."""
    return _token_fn(_cfg_key(c), float(temperature), fp8)(
        params, tokens, at, keys)


@functools.lru_cache(maxsize=None)
def _reward_fn(ckey, fp8):
    c = dict(ckey)

    def fn(params, tokens, length):
        h = _trunk(params, c, tokens, _causal(tokens.shape[0]), fp8)
        v = _mm(h[length - 1][None], params["value_head"], fp8)[0, 0]
        return jax.nn.sigmoid(v)

    return jax.jit(fn)


def prm_reward(params, c, tokens, length, fp8=False):
    """The PRM's reward at the last of ``length`` tokens."""
    return _reward_fn(_cfg_key(c), fp8)(params, tokens, length)


@functools.lru_cache(maxsize=None)
def _embed_fn(ckey, fp8):
    c = dict(ckey)

    def fn(params, tokens, length):
        T = tokens.shape[0]
        valid = jnp.arange(T) < length
        mask = jnp.broadcast_to(valid[None, :], (T, T))
        h = _trunk(params, c, tokens, mask, fp8)
        w = valid.astype(jnp.float32)[:, None]
        return (h * w).sum(0) / jnp.maximum(w.sum(), 1.0)

    return jax.jit(fn)


def embedding(params, c, tokens, length, fp8=False):
    """Mean of the embedder's final hidden states over ``length``
    tokens (bidirectional attention among them only)."""
    return _embed_fn(_cfg_key(c), fp8)(params, tokens, length)
