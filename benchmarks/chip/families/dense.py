"""The dense family: attention and a (gated) MLP in every layer, as the
configuration files of ``phi3-mini-3.8b`` and ``yi-6b`` state them.

A family module gives the harness everything that differs between
model families, and nothing else:

- ``model_config(c, name, max_seq_len)``: the program's ``ModelConfig``
  for a flat configuration dict (the generator's, or the PRM's), for a
  cell whose sequences reach ``max_seq_len`` tokens;
- ``leaf_init(name, shape, key)``: a weight leaf, or ``None`` for the
  common rule of ``weights.py``;
- ``token_logits`` and ``prm_reward``: the plain reference, with
  ``reference.py``'s signatures; it imports nothing of the program;
- ``dims(c)``, ``prefill``, ``decode`` and ``prm_score``: the required
  operations, with ``flops.py``'s signatures.  The generator's
  ``dims(c)`` is also what the reader ``tree_attn_roofline`` hands to
  ``flops.tree_attention_bytes``: it has to be a ``flops.Dims`` whose
  ``layers`` counts the layers that attend over the KV pages (in a
  hybrid, the attention calls alone), with that attention's ``heads``,
  ``kv_heads`` and ``head_dim``.

A configuration file names its family under ``"family"``; without it
the family is this one.  The embedder is always mapped by this
module's ``model_config`` with ``encoder=True``.
"""
from __future__ import annotations

from benchmarks.chip import flops as F
from benchmarks.chip import reference as R

token_logits = R.token_logits
prm_reward = R.prm_reward
dims = F.Dims.of
prefill = F.prefill
decode = F.decode
prm_score = F.prm_score


def leaf_init(name, shape, key):
    return None


def model_config(c: dict, name: str, max_seq_len: int, *,
                 encoder: bool = False):
    """Refuses a key that the mapping would drop while it changes what
    is served: tied embeddings, and a sliding window that a sequence of
    the cell would outrun (one at least as long attends in full)."""
    from repro.configs.base import ModelConfig
    if c.get("tie_word_embeddings"):
        raise ValueError(f"{name}: tie_word_embeddings is true, but the "
                         f"dense family serves a separate output head")
    window = c.get("sliding_window")
    if window and window < max_seq_len:
        raise ValueError(f"{name}: sliding_window {window} is shorter than "
                         f"the cell's max_seq_len {max_seq_len}, and the "
                         f"dense family attends in full")
    heads = c["num_attention_heads"]
    return ModelConfig(
        name=name, arch_type="encoder" if encoder else "dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=heads, n_kv_heads=c.get("num_key_value_heads", heads),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c.get("head_dim", 0),
        rope_theta=float(c.get("rope_theta", 10000.0)),
        norm_eps=float(c.get("rms_norm_eps", 1e-5)),
        act="swiglu" if c.get("hidden_act", "silu") == "silu" else "gelu",
        causal=not encoder, tie_embeddings=False,
        dtype=c.get("torch_dtype", "bfloat16"))
