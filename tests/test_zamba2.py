"""The Zamba2 hybrid block (models/hybrid.py) against a plain copy of its
equations, at tiny widths on seeded random weights.

The layout under test has two shared blocks used in turn over uneven
segments: hybrid layers 2, 5 and 9 of 12 (blocks A, B, A), two trailing
Mamba2 layers, grouped-query attention and two B/C groups.  The plain
copy below is written in float64 numpy with the sequential Mamba2
recurrence, independent of the program's chunked scan, paged pools and
kernels.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.special import erf

from repro.configs import get_config
from repro.configs.base import ModelConfig, SSMConfig
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, PagedEngine

CFG = ModelConfig(
    name="zamba2-test", arch_type="hybrid", n_layers=12, d_model=64,
    n_heads=4, n_kv_heads=2, d_ff=96, vocab_size=97, rope_theta=10000.0,
    shared_attn_params=True, hybrid_layer_ids=(2, 5, 9), num_mem_blocks=2,
    adapter_rank=4, act="gelu", tie_embeddings=True, dtype="float32",
    ssm=SSMConfig(kind="mamba2", d_state=8, d_conv=4, head_dim=16, expand=2,
                  chunk_size=8, n_groups=2))

# The program computes in float32 and the plain copy in float64: over
# 12 layers the program's logits (largest about 2.7) lie within 3.4e-5
# of it, as float32 rounding through the residual stream and the
# recurrence's exponentials gives.  TOL leaves six times that for
# another CPU's rounding; a trivialized mechanism moves the logits by
# 1 or more, and SENSITIVE asks for 500 TOL.
TOL = 2e-4
SENSITIVE = 0.1


@pytest.fixture(scope="module")
def model_params():
    model = build_model(CFG, remat=False)
    params = model.init(jax.random.key(0))
    # every leaf off its initial value (norm scales 1, zero biases, D 1)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    leaves = [a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
              for a, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves)


# ---------------------------------------------------------------------------
# The plain copy of the equations
# ---------------------------------------------------------------------------

def _rms(x, w, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    T, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    ang = np.arange(T)[:, None, None] * inv
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x2 * np.cos(ang) + x1 * np.sin(ang)], -1)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _mamba(mp, h, cfg, norm_groups):
    s = cfg.ssm
    T = h.shape[0]
    G, ds, hd = s.n_groups, s.d_state, s.head_dim
    d_in = s.expand * cfg.d_model
    H = d_in // hd
    z, xbc, dt = h @ mp["z_proj"], h @ mp["xbc_proj"], h @ mp["dt_proj"]
    K = s.d_conv
    ext = np.concatenate([np.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = _silu(sum(ext[i:i + T] * mp["conv_w"][i] for i in range(K))
                + mp["conv_b"])
    x = xbc[:, :d_in].reshape(T, H, hd)
    Bm = xbc[:, d_in:d_in + G * ds].reshape(T, G, ds)
    Cm = xbc[:, d_in + G * ds:].reshape(T, G, ds)
    dt = np.logaddexp(0.0, dt + mp["dt_bias"])
    A = -np.exp(mp["A_log"])
    S = np.zeros((H, hd, ds))
    y = np.zeros((T, H, hd))
    for t in range(T):
        for n in range(H):
            g = n // (H // G)
            S[n] = np.exp(dt[t, n] * A[n]) * S[n] \
                + dt[t, n] * np.outer(x[t, n], Bm[t, g])
            y[t, n] = S[n] @ Cm[t, g] + mp["D"][n] * x[t, n]
    y = (y.reshape(T, d_in) * _silu(z)).reshape(T, norm_groups, -1)
    y = _rms(y, 1.0).reshape(T, d_in) * mp["norm_w"]
    return y @ mp["out_proj"]


def _block(blk, hl, x, x0, cfg, adapters, join_x0):
    T = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    u = _rms(np.concatenate([x, x0 if join_x0 else np.zeros_like(x0)], -1),
             blk["ln_in"])
    a = blk["attn"]
    q = _rope((u @ a["wq"]).reshape(T, H, hd), cfg.rope_theta)
    k = _rope((u @ a["wk"]).reshape(T, K, hd), cfg.rope_theta)
    v = (u @ a["wv"]).reshape(T, K, hd)
    kh = np.repeat(k, H // K, axis=1)
    vh = np.repeat(v, H // K, axis=1)
    s = np.einsum("thd,chd->htc", q, kh) * (hd / 2) ** -0.5
    s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("htc,chd->thd", p, vh).reshape(T, H * hd) @ a["wo"]
    m = _rms(o, blk["ln_ff"])
    gu = m @ blk["mlp"]["w_gate_up"]
    if adapters:
        gu = gu + (m @ hl["adapter_a"]) @ hl["adapter_b"]
    g, pp = np.split(gu, 2, -1)
    gelu = 0.5 * g * (1.0 + erf(g / np.sqrt(2.0)))
    return (gelu * pp) @ blk["mlp"]["w_down"] @ hl["linear"]


def plain_logits(params, cfg, tokens, *, norm_groups=None, adapters=True,
                 join_x0=True):
    """(T, V) logits of one sequence; the keywords trivialize one
    mechanism each (the gated norm over one group, no adapters, zeros
    in place of x0)."""
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    pick = (lambda tree, i: jax.tree.map(lambda a: a[i], tree))
    x0 = P["embed"][np.asarray(tokens)]
    x = x0
    ids = cfg.hybrid_ids()
    for i in range(cfg.n_layers):
        h = x
        if i in ids:
            j = ids.index(i)
            h = x + _block(pick(P["shared"], j % cfg.num_mem_blocks),
                           pick(P["hybrid"], j), x, x0, cfg, adapters,
                           join_x0)
        lay = pick(P["groups"][0], i)
        x = x + _mamba(lay["mamba"], _rms(h, lay["ln"]), cfg,
                       norm_groups or cfg.ssm.n_groups)
    return _rms(x, P["ln_f"]) @ P["embed"].T


TOKENS = np.random.default_rng(0).integers(0, CFG.vocab_size, 21)


def test_layout_is_two_blocks_in_turn_over_uneven_segments(model_params):
    _, params = model_params
    assert CFG.hybrid_ids() == (2, 5, 9) and CFG.n_mem_blocks == 2
    assert params["shared"]["ln_in"].shape == (2, 2 * CFG.d_model)
    assert params["hybrid"]["linear"].shape == (3, CFG.d_model, CFG.d_model)
    assert params["groups"][0]["mamba"]["xbc_proj"].shape[-1] == \
        2 * CFG.d_model + 2 * 2 * 8


def test_full_forward_matches_the_equations(model_params):
    model, params = model_params
    got = np.asarray(model.forward(params, {"tokens": TOKENS[None]})[0][0])
    want = plain_logits(params, CFG, TOKENS)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)


@pytest.mark.parametrize("trivial", [dict(norm_groups=1),
                                     dict(adapters=False),
                                     dict(join_x0=False)],
                         ids=["norm_one_group", "no_adapter", "no_x0"])
def test_each_mechanism_moves_the_logits(model_params, trivial):
    """The grouped gated norm, the adapters and x0 each change the
    logits far beyond TOL, so the comparisons above would catch a
    program that left any of them out."""
    model, params = model_params
    got = np.asarray(model.forward(params, {"tokens": TOKENS[None]})[0][0])
    other = plain_logits(params, CFG, TOKENS, **trivial)
    assert np.abs(got - other).max() > SENSITIVE


def test_b_c_groups_are_used(model_params):
    """Heads of the second group read the second B/C slice: the same
    weights served as one group give other logits."""
    model, params = model_params
    one = dataclasses.replace(
        CFG, ssm=dataclasses.replace(CFG.ssm, n_groups=1),
        num_mem_blocks=2)
    g = params["groups"][0]["mamba"]
    d_in, ds = 2 * CFG.d_model, 8
    keep = np.r_[0:d_in + ds, d_in + 2 * ds:d_in + 3 * ds]   # group 0
    p1 = dict(params, groups=[dict(params["groups"][0], mamba=dict(
        g, xbc_proj=g["xbc_proj"][..., keep], conv_w=g["conv_w"][..., keep],
        conv_b=g["conv_b"][..., keep]))])
    got = np.asarray(model.forward(params, {"tokens": TOKENS[None]})[0][0])
    alt = np.asarray(build_model(one, remat=False).forward(
        p1, {"tokens": TOKENS[None]})[0][0])
    assert np.abs(got - alt).max() > SENSITIVE


# ---------------------------------------------------------------------------
# The paged engine against the full forward
# ---------------------------------------------------------------------------

def _steps(eng, since):
    return [np.asarray(a) for a in eng.logits_trace[since:]]


@pytest.mark.parametrize("attention", ["paged", "tree"])
def test_paged_prefill_decode_and_branch_match_full_forward(model_params,
                                                            attention):
    """Prefill, decode, then two children copying the parent's state
    page and decoding different tokens: every step's logits equal the
    full forward over the row's own tokens."""
    model, params = model_params
    eng = PagedEngine(model, params, EngineConfig(
        n_pages=64, page_size=4, max_batch=4, max_seq_len=64,
        attention=attention, trace_logits=True))
    prompt = [int(t) for t in TOKENS[:13]]
    sid = eng.prefill(prompt)
    eng.decode([sid], 3, jax.random.key(3), temperature=1.0)
    n_parent = len(eng.tokens[sid])
    kids = eng.branch(sid, 2)
    assert len({eng.state_of[k] for k in (sid, *kids)}) == 3
    mark = len(eng.logits_trace)
    eng.decode(kids, 5, row_keys=jax.random.split(jax.random.key(4), 2),
               temperature=1.0)
    seqs = [eng.tokens[k] for k in kids]
    assert seqs[0][n_parent:] != seqs[1][n_parent:]

    def full(seq):
        return np.asarray(model.forward(
            params, {"tokens": jnp.asarray([seq])})[0][0])

    trace = _steps(eng, 0)
    parent = full(eng.tokens[sid])
    # prefill: logits of the pending last prompt token's predecessor
    np.testing.assert_allclose(trace[0][0], parent[len(prompt) - 2],
                               rtol=0, atol=TOL)
    for k in range(3):
        np.testing.assert_allclose(trace[1 + k][0],
                                   parent[len(prompt) - 1 + k],
                                   rtol=0, atol=TOL)
    for r, seq in enumerate(seqs):
        f = full(seq)
        for k, step in enumerate(_steps(eng, mark)):
            np.testing.assert_allclose(step[r], f[n_parent - 1 + k],
                                       rtol=0, atol=TOL)
    eng.alloc.check_invariants()


def test_streamed_prefill_matches_full_forward(model_params):
    model, params = model_params
    eng = PagedEngine(model, params, EngineConfig(
        n_pages=64, page_size=4, max_batch=4, max_seq_len=64,
        attention="tree", trace_logits=True, prefill_chunk_tokens=8))
    prompt = [int(t) for t in TOKENS]
    sid = eng.prefill(prompt)
    eng.decode([sid], 2, jax.random.key(5), temperature=1.0)
    f = np.asarray(model.forward(
        params, {"tokens": jnp.asarray([eng.tokens[sid]])})[0][0])
    trace = _steps(eng, 0)
    for k, step in enumerate(trace):
        np.testing.assert_allclose(step[0], f[len(prompt) - 2 + k],
                                   rtol=0, atol=TOL)


# ---------------------------------------------------------------------------
# Counts of the configuration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers", [81, 7])
def test_param_count_is_the_built_model(layers):
    cfg = dataclasses.replace(get_config("zamba2-7b"), n_layers=layers)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == \
        cfg.param_count()


def test_state_and_kv_bytes_are_the_engine_pools(model_params):
    model, params = model_params
    eng = PagedEngine(model, params, EngineConfig(
        n_pages=16, page_size=4, max_batch=2, max_seq_len=32,
        attention="tree"))
    per_page = sum(a[:, 0].nbytes for a in eng.state.arrays.values())
    assert CFG.state_bytes_per_branch() == per_page
    pool_token = (eng.pool.k[:, 0, 0].nbytes + eng.pool.v[:, 0, 0].nbytes)
    assert CFG.kv_bytes_per_token(4) == pool_token
    z = get_config("zamba2-7b")
    # published sizes: 13 hybrid layers of 32 KV heads of 224, and
    # 112 heads of 64 x 64 plus a conv tail of 3 x 7424 in every layer
    assert z.kv_bytes_per_token(4) == 13 * 2 * 32 * 224 * 4
    assert z.state_bytes_per_branch() == 81 * (112 * 64 * 64 + 3 * 7424) * 4


def test_flops_count_every_use_of_a_shared_block():
    z = get_config("zamba2-7b")
    block, per_layer = z._hybrid_block_params()
    uses = len(z.hybrid_ids())
    assert z.flops_per_token() == 2.0 * (
        z.param_count() + (uses - z.n_mem_blocks) * block)
    assert z.flops_per_token(100) - z.flops_per_token() == \
        4.0 * uses * z.n_heads * z.head_dim * 100
