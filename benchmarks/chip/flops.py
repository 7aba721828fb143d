"""Operations and bytes the search needs, computed from shapes.

These are required counts, not what the compiled programs execute:
padding rows and padded positions count for nothing, and a multiply-add
is two operations.  ``Dims`` holds one model's sizes as the
configuration file states them.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool = True          # SwiGLU (three matrices) or GELU (two)

    @classmethod
    def of(cls, c: dict) -> "Dims":
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads,
                   kv_heads=c.get("num_key_value_heads", heads),
                   head_dim=c.get("head_dim",
                                  c["hidden_size"] // heads),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=c.get("hidden_act", "silu") == "silu")

    @property
    def layer_params(self) -> int:
        """Matrix parameters of one layer (norm vectors left out)."""
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.heads * hd + 2 * d * self.kv_heads * hd
        return attn + (3 if self.gated else 2) * d * self.d_ff

    def dense_per_token(self) -> int:
        return 2 * self.layers * self.layer_params

    def attn_per_pair(self) -> int:
        """One query against one key, all layers: scores and values."""
        return 4 * self.layers * self.heads * self.head_dim


def prefill(dims: Dims, n_ctx: int) -> int:
    """Prefill of ``n_ctx`` prompt tokens into the cache (causal), plus
    the output head at the last position."""
    if n_ctx <= 0:
        return 0
    pairs = n_ctx * (n_ctx + 1) // 2
    return (dims.dense_per_token() * n_ctx + dims.attn_per_pair() * pairs
            + 2 * dims.d_model * dims.vocab)


def decode(dims: Dims, n_tokens: int, keys_sum: int) -> int:
    """``n_tokens`` decoded tokens whose queries attend ``keys_sum``
    keys in total, with the output head for each."""
    return ((dims.dense_per_token() + 2 * dims.d_model * dims.vocab)
            * n_tokens + dims.attn_per_pair() * keys_sum)


def prm_score(dims: Dims, n: int) -> int:
    """The value-head PRM over one sequence of ``n`` tokens (causal),
    with the scalar head at every position."""
    return (dims.dense_per_token() * n
            + dims.attn_per_pair() * (n * (n + 1) // 2)
            + 2 * dims.d_model * n)


def embed(dims: Dims, n: int) -> int:
    """The bidirectional encoder over one step of ``n`` tokens."""
    return dims.dense_per_token() * n + dims.attn_per_pair() * n * n


def tree_attention_bytes(dims: Dims, page_size: int, unique_pages: int,
                         rows: int, bytes_per_el: int = 4) -> int:
    """Bytes one tree-attention pass must move over all layers: every
    unique page's keys and values once, each row's query in and output
    out."""
    page = page_size * dims.kv_heads * dims.head_dim * bytes_per_el
    qo = 2 * rows * dims.heads * dims.head_dim * bytes_per_el
    return dims.layers * (2 * page * unique_pages + qo)
