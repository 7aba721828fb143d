"""Zamba2-7B [arXiv:2411.15242] — Mamba2 backbone + shared attention blocks.

Published values (hf: Zyphra/Zamba2-7B-Instruct, config.json): 81
layers, each a Mamba2 layer (112 heads of 64, d_state 64, 2 groups of
B/C, conv 4, expand 2, chunk 256); at the 13 ``hybrid_layer_ids`` one
of two shared transformer blocks, in turn, attends over the hidden
state joined to the input embeddings (7168 wide, 32 heads of 224, RoPE
theta 10000), runs a gated-GELU MLP of 14336 with a rank-128 adapter
per hybrid layer, and feeds that layer's Mamba2 input through a
per-layer 3584 x 3584 linear.  Tied embeddings (the Zamba2Config
default), vocabulary 32000.
"""
from .base import ModelConfig, SSMConfig, register

register(ModelConfig(
    name="zamba2-7b",
    arch_type="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,            # attention_head_dim = 2 * hidden / heads
    d_ff=14336,              # shared block's MLP
    vocab_size=32000,
    rope_theta=10000.0,
    shared_attn_params=True,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,        # use_shared_mlp_adapter; no attention adapter
    ssm=SSMConfig(kind="mamba2", d_state=64, d_conv=4, head_dim=64,
                  expand=2, chunk_size=256, n_groups=2),
    act="gelu",              # gated, exact (erf) GELU
    tie_embeddings=True,
    # long-context: the shared attention block switches to SWA (window 4096)
    # *only* in long mode so the 500k decode cache stays O(window); mamba
    # state is O(1).  Normal serving uses full attention.  See DESIGN.md.
    long_context_window=4096,
    long_context_mode="recurrent",
    citation="arXiv:2411.15242",
))
