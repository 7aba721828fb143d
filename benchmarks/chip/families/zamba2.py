"""The Zamba2 family (arXiv:2411.15242; hf Zyphra/Zamba2-7B-Instruct):
every layer a Mamba2 layer, and at the hybrid layers a shared
transformer block, one of ``num_mem_blocks`` in turn, that reads the
hidden state joined to the input embeddings and feeds the layer's
Mamba2 input.  The layer equations, with ``E`` the embedding and
``x0 = E[token]``::

    for each layer i:
      if i is the j-th hybrid layer (block b = j mod num_mem_blocks):
        u = RMSNorm_b,in([x ; x0])                          # width 2d
        a = Wo_b . softmax(RoPE(q) RoPE(k)^T (hd/2)^-0.5, causal) v
        m = RMSNorm_b,ff(a)                                 # no residual
        g, p = split(m Wgu_b + (m A_j) B_j)                 # adapter
        t = Wlin_j . (Wdown_b . (gelu_erf(g) * p))
        x = x + Mamba2_i(RMSNorm_i(x + t))
      else:
        x = x + Mamba2_i(RMSNorm_i(x))
    logits = RMSNorm_f(x) E^T                               # tied

    Mamba2(h): z = h Wz; xBC = silu(conv1d_4(h Wxbc) + b); dt =
      softplus(h Wdt + dt_bias); A = -exp(A_log); head n reads B, C of
      group floor(n / (heads / groups));
      S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t;  y_t = C_t . S_t + D x_t;
      out = GroupRMSNorm(y * silu(z)) Wout     # mean square per group

The reference below writes the Mamba2 part as that sequential
recurrence, in float32 at full matmul precision, and imports nothing
of the program.  It reads the weights in the program's layout:
``groups[0]`` stacks each layer's ``ln`` and ``mamba`` (``z_proj``,
``xbc_proj``, ``dt_proj``, ``conv_w``, ``conv_b``, ``A_log``,
``dt_bias``, ``D``, ``norm_w``, ``out_proj``), ``shared`` the blocks
held, ``hybrid`` each hybrid layer's ``linear``, ``adapter_a`` and
``adapter_b``.

The published layout (``hybrid_layer_ids`` and ``layers_block_type``)
is held here: the harness hands a family only a configuration's flat
numbers, and a cut depth keeps the ids below ``num_hidden_layers``.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmarks.chip import reference as R

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)
LAYERS_BLOCK_TYPE = tuple("hybrid" if i in HYBRID_LAYER_IDS else "mamba"
                          for i in range(81))

# settings the program has no path for, with the value it serves
_FIXED = {"use_shared_attention_adapter": False, "use_long_context": False,
          "add_bias_linear": False, "use_conv_bias": True,
          "use_mem_rope": True, "hidden_act": "gelu",
          "tie_word_embeddings": True}


def hybrid_ids(c: dict) -> tuple:
    return tuple(i for i in HYBRID_LAYER_IDS if i < c["num_hidden_layers"])


def _check(c: dict, name: str) -> None:
    for k, v in _FIXED.items():
        if c.get(k, v) != v:
            raise ValueError(f"{name}: {k} is {c[k]!r}; the zamba2 family "
                             f"serves {v!r}")
    d = c["hidden_size"]
    if c["attention_hidden_size"] != 2 * d or \
            c["n_mamba_heads"] * c["mamba_headdim"] != c["mamba_expand"] * d:
        raise ValueError(f"{name}: widths differ from the Zamba2 block")


def model_config(c: dict, name: str, max_seq_len: int):
    from repro.configs.base import ModelConfig, SSMConfig
    _check(c, name)
    heads = c["num_attention_heads"]
    return ModelConfig(
        name=name, arch_type="hybrid", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=heads,
        n_kv_heads=c.get("num_key_value_heads", heads),
        head_dim=c["attention_head_dim"], d_ff=c["ffn_hidden_size"],
        vocab_size=c["vocab_size"], rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]), shared_attn_params=True,
        hybrid_layer_ids=HYBRID_LAYER_IDS,
        num_mem_blocks=c["num_mem_blocks"],
        adapter_rank=c["adapter_rank"] if c["use_shared_mlp_adapter"] else 0,
        ssm=SSMConfig(kind="mamba2", d_state=c["mamba_d_state"],
                      d_conv=c["mamba_d_conv"], head_dim=c["mamba_headdim"],
                      expand=c["mamba_expand"], chunk_size=c["chunk_size"],
                      n_groups=c["mamba_ngroups"]),
        act="gelu", tie_embeddings=True,
        dtype=c.get("torch_dtype", "bfloat16"))


# time-step range of the published initialization (time_step_min/max)
DT_MIN, DT_MAX = 1e-3, 1e-1


def leaf_init(name, shape, key):
    """Mamba2's own initialization where the common rule has none:
    decay rates A in [1, 16], softplus(dt_bias) log-uniform in
    [DT_MIN, DT_MAX], D = 1, the conv bias as a depthwise Conv1d's
    default U(-1/2, 1/2) (fan-in 4), gated-norm scales 1."""
    last = name.rsplit("/", 1)[-1]
    if last == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if last == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        jnp.log(DT_MIN), jnp.log(DT_MAX)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if last in ("D", "norm_w"):
        return jnp.ones(shape, jnp.float32)
    if last == "conv_b":
        return jax.random.uniform(key, shape, jnp.float32, -0.5, 0.5)
    return None


# ---------------------------------------------------------------------------
# The plain reference
# ---------------------------------------------------------------------------

def _mamba(mp, h, c, fp8):
    """One Mamba2 mixer over a sequence h (T, d), state from zero."""
    T = h.shape[0]
    G, ds = c["mamba_ngroups"], c["mamba_d_state"]
    H, hd = c["n_mamba_heads"], c["mamba_headdim"]
    d_in = H * hd
    z = R._mm(h, mp["z_proj"], fp8)
    xbc = R._mm(h, mp["xbc_proj"], fp8)
    dt = R._mm(h, mp["dt_proj"], fp8)
    K = mp["conv_w"].shape[0]
    ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc], 0)
    conv = sum(ext[i:i + T] * mp["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + mp["conv_b"])
    xs = xbc[:, :d_in].reshape(T, G, H // G, hd)
    Bm = xbc[:, d_in:d_in + G * ds].reshape(T, G, ds)
    Cm = xbc[:, d_in + G * ds:].reshape(T, G, ds)
    dt = jax.nn.softplus(dt + mp["dt_bias"]).reshape(T, G, H // G)
    A = -jnp.exp(mp["A_log"]).reshape(G, H // G)

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = jnp.exp(dt_t * A)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return S, jnp.einsum("ghpn,gn->ghp", S, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((G, H // G, hd, ds)),
                        (xs, Bm, Cm, dt))
    y = (y + mp["D"].reshape(G, H // G)[:, :, None] * xs).reshape(T, d_in)
    y = (y * jax.nn.silu(z)).reshape(T, G, d_in // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + c["rms_norm_eps"])
    return R._mm(y.reshape(T, d_in) * mp["norm_w"], mp["out_proj"], fp8)


def _block(blk, hl, x, x0, c, fp8):
    """The shared block's injection t (T, d) for one hybrid layer."""
    T = x.shape[0]
    H, hd = c["num_attention_heads"], c["attention_head_dim"]
    K = c.get("num_key_value_heads", H)
    eps, pos = c["rms_norm_eps"], jnp.arange(T)
    u = R._rms(blk["ln_in"], jnp.concatenate([x, x0], -1), eps)
    a = blk["attn"]
    q = R._rope(R._mm(u, a["wq"], fp8).reshape(T, H, hd), pos, c["rope_theta"])
    k = R._rope(R._mm(u, a["wk"], fp8).reshape(T, K, hd), pos, c["rope_theta"])
    v = R._mm(u, a["wv"], fp8).reshape(T, K, hd)
    q = q.reshape(T, K, H // K, hd)
    s = R._ein("tkgh,ckh->kgtc", q, k, fp8) * (hd / 2) ** -0.5
    s = jnp.where(R._causal(T)[None, None], s, -jnp.inf)
    o = R._ein("kgtc,ckh->tkgh", jax.nn.softmax(s, -1), v, fp8, b_axis=0)
    m = R._rms(blk["ln_ff"], R._mm(o.reshape(T, H * hd), a["wo"], fp8), eps)
    gu = R._mm(m, blk["mlp"]["w_gate_up"], fp8)
    if "adapter_a" in hl:
        gu = gu + R._mm(R._mm(m, hl["adapter_a"], fp8), hl["adapter_b"], fp8)
    g, p = jnp.split(gu, 2, -1)
    t = R._mm(jax.nn.gelu(g, approximate=False) * p, blk["mlp"]["w_down"], fp8)
    return R._mm(t, hl["linear"], fp8)


def _trunk(params, c, tokens, fp8):
    """Final-norm hidden states (T, d) of one sequence."""
    emb = R._q8(params["embed"]) if fp8 else params["embed"]
    x0 = x = emb[tokens].astype(jnp.float32)
    ids = hybrid_ids(c)
    g = params["groups"][0]
    for i in range(c["num_hidden_layers"]):
        h = x
        if i in ids:
            j = ids.index(i)
            pick = (lambda tree, n: jax.tree.map(lambda a: a[n], tree))
            h = x + _block(pick(params["shared"], j % c["num_mem_blocks"]),
                           pick(params["hybrid"], j), x, x0, c, fp8)
        lay = jax.tree.map(lambda a: a[i], g)
        x = x + _mamba(lay["mamba"], R._rms(lay["ln"], h, c["rms_norm_eps"]),
                       c, fp8)
    return R._rms(params["ln_f"], x, c["rms_norm_eps"]), emb


@functools.lru_cache(maxsize=None)
def _token_fn(ckey, temperature, fp8):
    c = dict(ckey)

    def fn(params, tokens, at, keys):
        with jax.default_matmul_precision("highest"):
            h, emb = _trunk(params, c, tokens, fp8)
            lg = R._mm(h[at - 1], emb.T, fp8)
        if temperature > 0:
            noise = jax.vmap(lambda k: jax.random.gumbel(
                k, (lg.shape[-1],), jnp.float32))(keys)
            lg = lg / temperature + noise
        return lg

    return jax.jit(fn)


def token_logits(params, c, tokens, at, keys, temperature, fp8=False):
    """Noisy next-token scores (n, V) at the positions ``at`` of one
    padded sequence, ``keys`` being each position's sampling key."""
    return _token_fn(R._cfg_key(c), float(temperature), fp8)(
        params, tokens, at, keys)


@functools.lru_cache(maxsize=None)
def _reward_fn(ckey, fp8):
    c = dict(ckey)

    def fn(params, tokens, length):
        with jax.default_matmul_precision("highest"):
            h, _ = _trunk(params, c, tokens, fp8)
            v = R._mm(h[length - 1][None], params["value_head"], fp8)[0, 0]
        return jax.nn.sigmoid(v)

    return jax.jit(fn)


def prm_reward(params, c, tokens, length, fp8=False):
    """The PRM's reward at the last of ``length`` tokens."""
    return _reward_fn(R._cfg_key(c), fp8)(params, tokens, length)


# ---------------------------------------------------------------------------
# Required operations and bytes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Dims:
    """One Zamba2 model's sizes.  ``layers``, ``heads``, ``kv_heads``
    and ``head_dim`` describe the attention over the KV pages alone
    (one call per hybrid layer), as ``flops.tree_attention_bytes``
    reads them."""
    layers: int                 # hybrid layers kept
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    mamba_layers: int
    mamba_heads: int
    mamba_head_dim: int
    d_state: int
    n_groups: int
    d_conv: int
    adapter_rank: int
    blocks: int                 # shared blocks held

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def d_xbc(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    @property
    def mamba_matrix_params(self) -> int:
        d = self.d_model
        return d * (self.d_inner + self.d_xbc + self.mamba_heads) \
            + self.d_inner * d

    @property
    def block_matrix_params(self) -> int:
        d, a = self.d_model, 2 * self.d_model
        hd = self.head_dim
        return (a * (self.heads + 2 * self.kv_heads) * hd
                + self.heads * hd * d + 3 * d * self.d_ff)

    @property
    def hybrid_matrix_params(self) -> int:
        return self.d_model ** 2 \
            + self.adapter_rank * (self.d_model + 2 * self.d_ff)

    def per_token(self) -> int:
        """Operations per token outside attention over keys and the
        output head: every matrix product, the depthwise conv, and the
        recurrence's two contractions (x (x) B and C . S)."""
        ssd = 4 * self.mamba_heads * self.mamba_head_dim * self.d_state
        mamba = 2 * self.mamba_matrix_params + 2 * self.d_conv * self.d_xbc \
            + ssd
        blocks = 2 * (self.block_matrix_params + self.hybrid_matrix_params)
        return self.mamba_layers * mamba + self.layers * blocks

    def attn_per_pair(self) -> int:
        return 4 * self.layers * self.heads * self.head_dim

    def weight_params(self) -> int:
        """Every parameter of the generator (tied head)."""
        d, di, x = self.d_model, self.d_inner, self.d_xbc
        mamba = self.mamba_matrix_params + d + (self.d_conv + 1) * x \
            + 3 * self.mamba_heads + di
        block = self.block_matrix_params + 3 * d
        return (self.vocab * d + d + self.mamba_layers * mamba
                + self.blocks * block + self.layers * self.hybrid_matrix_params)

    def state_bytes(self, bytes_per_el: int = 4) -> int:
        """One sequence's Mamba2 state, every layer: h and the conv tail."""
        per = self.mamba_heads * self.mamba_head_dim * self.d_state \
            + (self.d_conv - 1) * self.d_xbc
        return self.mamba_layers * per * bytes_per_el


def dims(c: dict) -> Dims:
    n_h = len(hybrid_ids(c))
    return Dims(
        layers=n_h, d_model=c["hidden_size"],
        heads=c["num_attention_heads"],
        kv_heads=c.get("num_key_value_heads", c["num_attention_heads"]),
        head_dim=c["attention_head_dim"], d_ff=c["ffn_hidden_size"],
        vocab=c["vocab_size"], mamba_layers=c["num_hidden_layers"],
        mamba_heads=c["n_mamba_heads"], mamba_head_dim=c["mamba_headdim"],
        d_state=c["mamba_d_state"], n_groups=c["mamba_ngroups"],
        d_conv=c["mamba_d_conv"],
        adapter_rank=c["adapter_rank"] if c["use_shared_mlp_adapter"] else 0,
        blocks=min(c["num_mem_blocks"], n_h))


def prefill(d: Dims, n_ctx: int) -> int:
    """``flops.prefill`` for the hybrid: causal attention at the hybrid
    layers, the tied head at the last position."""
    if n_ctx <= 0:
        return 0
    return (d.per_token() * n_ctx + d.attn_per_pair() * (n_ctx * (n_ctx + 1)
                                                         // 2)
            + 2 * d.d_model * d.vocab)


def decode(d: Dims, n_tokens: int, keys_sum: int) -> int:
    return ((d.per_token() + 2 * d.d_model * d.vocab) * n_tokens
            + d.attn_per_pair() * keys_sum)


def prm_score(d: Dims, n: int) -> int:
    return (d.per_token() * n + d.attn_per_pair() * (n * (n + 1) // 2)
            + 2 * d.d_model * n)


def decode_bytes(d: Dims, page_size: int, iterations: int, rows: int,
                 unique_pages: int, bytes_per_el: int = 4) -> int:
    """Bytes the decode program must move, in float32: the generator's
    weights once an iteration, each decoded row's Mamba2 state read and
    written once, and each unique KV page's keys and values once per
    hybrid layer."""
    page = page_size * d.kv_heads * d.head_dim * bytes_per_el
    return (d.weight_params() * bytes_per_el * iterations
            + 2 * d.state_bytes(bytes_per_el) * rows
            + d.layers * 2 * page * unique_pages)

