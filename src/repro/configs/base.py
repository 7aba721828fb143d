"""Config system: architecture configs, input shapes, and the registry.

Every assigned architecture gets one file in this package defining a
``ModelConfig`` with the exact public-literature numbers and registering it
under its assigned id.  Configs are plain dataclasses (no jax import) so that
importing them never touches device state.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

ARCH_TYPES = ("dense", "moe", "ssm", "hybrid", "encoder", "vlm")


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts FFN configuration."""

    n_experts: int = 8             # routed experts
    n_shared_experts: int = 0      # always-on experts (DeepSeekMoE)
    top_k: int = 2
    d_expert: int = 0              # per-expert hidden dim (0 => use d_ff)
    router_jitter: float = 0.0
    load_balance_coef: float = 0.01
    capacity_factor: float = 1.25  # used by dense-dispatch einsum MoE


@dataclass(frozen=True)
class SSMConfig:
    """State-space / linear-attention mixer configuration."""

    kind: str = "mamba2"           # "mamba2" | "rwkv6"
    d_state: int = 64              # recurrent state size per head-channel
    d_conv: int = 4                # depthwise conv width (mamba)
    head_dim: int = 64             # SSD / WKV head dim
    expand: int = 2                # mamba inner expansion factor
    chunk_size: int = 128          # chunked-scan block length
    n_groups: int = 1              # mamba B/C groups (heads split evenly)


@dataclass(frozen=True)
class ModelConfig:
    """A single architecture's full configuration."""

    name: str
    arch_type: str                 # one of ARCH_TYPES
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0              # 0 => d_model // n_heads
    # --- attention details ---
    rope_theta: float = 10000.0
    qk_norm: bool = False
    causal: bool = True
    sliding_window: int = 0        # 0 => full attention
    mrope_sections: Tuple[int, ...] = ()   # VLM M-RoPE (t, h, w) splits
    # --- hybrid layout (Zamba2: every layer is a Mamba2 layer; at the
    # hybrid ids a shared attention+MLP block feeds its input) ---
    attn_every: int = 0            # >0 and no ids: ids k-1, 2k-1, ...
    shared_attn_params: bool = False  # blocks shared across hybrid layers
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 1        # shared blocks, used in turn
    adapter_rank: int = 0          # per-hybrid-layer MLP adapter (0: none)
    long_context_window: int = 0   # SWA window applied only in long mode
    frontend_dim: int = 0          # stubbed modality frontend embed dim
    # --- subsystem configs ---
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # --- misc ---
    norm_eps: float = 1e-5
    act: str = "swiglu"            # "swiglu" | "gelu"
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    # long-context policy: "swa" archs can serve long_500k with window cache
    long_context_mode: str = "none"   # "none" | "swa" | "recurrent"
    citation: str = ""

    # ------------------------------------------------------------------
    def __post_init__(self):
        assert self.arch_type in ARCH_TYPES, self.arch_type
        if self.head_dim == 0 and self.n_heads > 0:
            object.__setattr__(self, "head_dim",
                               self.attn_in_dim // self.n_heads)
        if self.arch_type == "hybrid":
            ids = self.hybrid_ids()
            assert ids == tuple(sorted(set(ids))) and all(
                0 <= i < self.n_layers for i in ids), ids
            assert self.ssm.kind == "mamba2", self.ssm
            d_in = self.ssm.expand * self.d_model
            assert (d_in // self.ssm.head_dim) % self.ssm.n_groups == 0

    # ------------------------------------------------------------------
    # hybrid (Zamba2) layout
    # ------------------------------------------------------------------
    @property
    def attn_in_dim(self) -> int:
        """Width attention reads: a hybrid's shared block attends over
        the hidden state joined to the input embeddings."""
        return 2 * self.d_model if self.arch_type == "hybrid" \
            else self.d_model

    @property
    def attn_scale(self) -> float:
        """Softmax scale; Zamba2 scales by (head_dim / 2) ** -0.5."""
        if self.arch_type == "hybrid":
            return (self.head_dim / 2) ** -0.5
        return self.head_dim ** -0.5

    def hybrid_ids(self) -> Tuple[int, ...]:
        """Layers whose Mamba2 input takes a shared block's output:
        ``hybrid_layer_ids`` below ``n_layers``, else every
        ``attn_every``-th layer (ids k-1, 2k-1, ...)."""
        if self.arch_type != "hybrid":
            return ()
        if self.hybrid_layer_ids:
            return tuple(i for i in self.hybrid_layer_ids
                         if i < self.n_layers)
        k = self.attn_every
        return tuple(range(k - 1, self.n_layers, k)) if k > 0 else ()

    @property
    def n_mem_blocks(self) -> int:
        """Shared blocks held: hybrid layer j uses block j mod this."""
        n = len(self.hybrid_ids())
        return min(self.num_mem_blocks, n) if self.shared_attn_params \
            else n

    # ------------------------------------------------------------------
    # derived quantities used by the roofline + the memory cost simulator
    # ------------------------------------------------------------------
    @property
    def is_attention_free(self) -> bool:
        return self.arch_type == "ssm"

    @property
    def supports_decode(self) -> bool:
        return self.arch_type != "encoder"

    @property
    def supports_long_context(self) -> bool:
        return self.long_context_mode != "none"

    def layer_plan(self) -> list:
        """Return [(kind, count), ...] describing homogeneous layer groups.

        kinds: 'attn' (attention+ffn), 'mamba' (mamba mixer), 'hybrid'
        (a Mamba2 layer each, fed by a shared attention block at
        ``hybrid_ids()``), 'wkv' (rwkv6 mixer + channel-mix).  Groups
        with count>1 are scanned over stacked params.
        """
        if self.arch_type == "ssm":
            kind = self.ssm.kind if self.ssm is not None else "rwkv6"
            return [("mamba" if kind == "mamba2" else "wkv",
                     self.n_layers)]
        if self.arch_type == "hybrid":
            return [("hybrid", self.n_layers)]   # ids: hybrid_ids()
        return [("attn", self.n_layers)]

    # -- parameter count (analytic, matches the model builders) ---------
    def param_count(self) -> int:
        d, hd = self.d_model, self.head_dim
        nh, nkv = self.n_heads, self.n_kv_heads
        emb = self.vocab_size * d
        out = 0 if self.tie_embeddings else self.vocab_size * d
        n = emb + out + d  # final norm

        def attn_params() -> int:
            p = d * nh * hd + 2 * d * nkv * hd + nh * hd * d  # q,k,v,o
            if self.qk_norm:
                p += 2 * hd
            return p + d  # pre-norm

        def ffn_dense(dff: int) -> int:
            mult = 3 if self.act == "swiglu" else 2
            return mult * d * dff + d  # + pre-norm

        if self.arch_type in ("dense", "vlm", "encoder"):
            n += self.n_layers * (attn_params() + ffn_dense(self.d_ff))
        elif self.arch_type == "moe":
            m = self.moe
            de = m.d_expert or self.d_ff
            per = attn_params()
            per += (m.n_experts + m.n_shared_experts) * 3 * d * de
            per += d * m.n_experts  # router
            per += d  # ffn pre-norm
            n += self.n_layers * per
        elif self.arch_type == "ssm":
            s = self.ssm
            if s is not None and s.kind == "mamba2":
                per = self._mamba_layer_params()
            else:
                # rwkv6: time-mix (r,k,v,g,o ~ 5 d^2) + channel mix
                per = 5 * d * d + 2 * d * self.d_ff + 2 * d
                per += 6 * d  # decay/bonus/token-shift params (approx)
            n += self.n_layers * per
        elif self.arch_type == "hybrid":
            block, per_hybrid = self._hybrid_block_params()
            n += self.n_layers * self._mamba_layer_params()
            n += self.n_mem_blocks * block
            n += len(self.hybrid_ids()) * per_hybrid
        return n

    def _mamba_dims(self) -> Tuple[int, int, int]:
        """(d_in, heads, d_xbc) of one Mamba2 mixer."""
        s = self.ssm
        d_in = s.expand * self.d_model
        return d_in, d_in // s.head_dim, d_in + 2 * s.n_groups * s.d_state

    def _mamba_layer_params(self) -> int:
        """One Mamba2 layer: pre-norm, z/xBC/dt projections, conv weight
        and bias, A_log/dt_bias/D, gated norm and output projection."""
        d, s = self.d_model, self.ssm
        d_in, H, d_xbc = self._mamba_dims()
        return (d + d * (d_in + d_xbc + H) + (s.d_conv + 1) * d_xbc
                + 3 * H + d_in + d_in * d)

    def _hybrid_block_params(self) -> Tuple[int, int]:
        """(one shared block, what each hybrid layer adds: its linear
        and MLP adapter).  A block is the norm over [x ; x0], q/k/v from
        that width, the output projection, the MLP norm and a gated MLP
        with its up and gate projections fused."""
        d, hd, f = self.d_model, self.head_dim, self.d_ff
        a = self.attn_in_dim
        block = (a + a * (self.n_heads + 2 * self.n_kv_heads) * hd
                 + self.n_heads * hd * d + d + d * 2 * f + f * d)
        per = d * d + self.adapter_rank * (d + 2 * f)
        return block, per

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed top-k experts)."""
        if self.arch_type != "moe":
            return self.param_count()
        m = self.moe
        de = m.d_expert or self.d_ff
        inactive = (m.n_experts - m.top_k) * 3 * self.d_model * de
        return self.param_count() - self.n_layers * inactive

    def kv_bytes_per_token(self, bytes_per_el: int = 2) -> int:
        """KV-cache bytes appended per decoded token (attention layers)."""
        if self.arch_type == "ssm":
            return 0
        n_attn_layers = self.n_layers
        if self.arch_type == "hybrid":
            n_attn_layers = len(self.hybrid_ids())
        return n_attn_layers * 2 * self.n_kv_heads * self.head_dim * bytes_per_el

    def state_bytes_per_branch(self, bytes_per_el: int = 4) -> int:
        """Recurrent-state bytes per live branch (SSM/hybrid)."""
        if self.arch_type not in ("ssm", "hybrid"):
            return 0
        s = self.ssm
        if s.kind == "rwkv6":
            n_heads = self.d_model // s.head_dim
            per_layer = n_heads * s.head_dim * s.head_dim + 2 * self.d_model
        else:  # mamba2: SSD state h and the conv tail of xBC
            _, n_heads, d_xbc = self._mamba_dims()
            per_layer = n_heads * s.head_dim * s.d_state \
                + (s.d_conv - 1) * d_xbc
        # a hybrid's every layer has a Mamba2 mixer
        return self.n_layers * per_layer * bytes_per_el

    def flops_per_token(self, seq_len: int = 0) -> float:
        """Approximate forward FLOPs per token (6ND/3 = 2ND + attention).

        A hybrid applies a shared block at every hybrid layer, so its
        blocks count once per use, not once per copy held."""
        base = 2.0 * self.active_param_count()
        n_attn = self.n_layers
        if self.arch_type == "hybrid":
            block, _ = self._hybrid_block_params()
            n_attn = len(self.hybrid_ids())
            base += 2.0 * (n_attn - self.n_mem_blocks) * block
        if seq_len and not self.is_attention_free:
            w = seq_len if not self.sliding_window else min(seq_len, self.sliding_window)
            base += 2.0 * 2.0 * n_attn * self.n_heads * self.head_dim * w
        return base


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ModelConfig) -> ModelConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate config {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    from . import _ensure_loaded  # noqa: avoid circular at module import
    _ensure_loaded()
    return _REGISTRY[name]


def list_configs() -> list:
    from . import _ensure_loaded
    _ensure_loaded()
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Reduced variants for CPU smoke tests
# ---------------------------------------------------------------------------

def tiny_variant(cfg: ModelConfig) -> ModelConfig:
    """Reduced same-family config: 2 layers, d_model<=512, <=4 experts."""
    d_model = min(cfg.d_model, 256)
    head_dim = 32
    n_heads = max(d_model // 64, 2)
    n_kv = max(1, min(cfg.n_kv_heads, n_heads))
    # keep the GQA ratio flavour: if original had fewer kv heads, halve
    if cfg.n_kv_heads < cfg.n_heads:
        n_kv = max(1, n_heads // 2)
    kw = dict(
        name=cfg.name + "-tiny",
        arch_type=cfg.arch_type,
        n_layers=2 if cfg.arch_type != "hybrid" else 7,
        d_model=d_model,
        n_heads=n_heads,
        n_kv_heads=n_kv,
        head_dim=head_dim,
        d_ff=min(cfg.d_ff, 512),
        vocab_size=min(cfg.vocab_size, 512),
        rope_theta=cfg.rope_theta,
        qk_norm=cfg.qk_norm,
        causal=cfg.causal,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        long_context_window=(min(cfg.long_context_window, 64)
                             if cfg.long_context_window else 0),
        frontend_dim=64 if cfg.frontend_dim else 0,
        mrope_sections=(8, 4, 4) if cfg.mrope_sections else (),
        attn_every=cfg.attn_every if cfg.arch_type == "hybrid" else 0,
        shared_attn_params=cfg.shared_attn_params,
        # a hybrid keeps two blocks in turn over uneven segments
        hybrid_layer_ids=(1, 4, 5) if cfg.hybrid_layer_ids else (),
        num_mem_blocks=cfg.num_mem_blocks,
        adapter_rank=min(cfg.adapter_rank, 8),
        norm_eps=cfg.norm_eps,
        act=cfg.act,
        dtype="float32",
        long_context_mode=cfg.long_context_mode,
    )
    if cfg.moe is not None:
        kw["moe"] = MoEConfig(
            n_experts=min(cfg.moe.n_experts, 4),
            n_shared_experts=min(cfg.moe.n_shared_experts, 1),
            top_k=min(cfg.moe.top_k, 2),
            d_expert=min(cfg.moe.d_expert or kw["d_ff"], 128),
            # dropless at test scale so chunk/step paths agree exactly
            capacity_factor=float(min(cfg.moe.n_experts, 4)),
        )
    if cfg.ssm is not None:
        kw["ssm"] = SSMConfig(
            kind=cfg.ssm.kind,
            d_state=min(cfg.ssm.d_state, 16),
            d_conv=cfg.ssm.d_conv,
            head_dim=32,
            expand=cfg.ssm.expand,
            chunk_size=32,
            n_groups=cfg.ssm.n_groups,
        )
    return ModelConfig(**kw)
