"""Operation and byte counts against hand counts at a tiny size."""
from benchmarks.chip import flops as F

# d=8, 2 heads of 4, 1 kv head, d_ff 16, vocab 10, 2 layers
D = F.Dims(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16,
           vocab=10)


def test_layer_params():
    # q and o: 8*8 each; k and v: 8*4 each; gated MLP: 3 * 8*16
    assert D.layer_params == 64 + 64 + 32 + 32 + 384
    gelu = F.Dims(2, 8, 2, 1, 4, 16, 10, gated=False)
    assert gelu.layer_params == 192 + 256


def test_decode_and_prefill_counts():
    dense = 2 * 2 * 576          # 2 ops per weight, 2 layers
    head = 2 * 8 * 10
    pair = 4 * 2 * 2 * 4         # scores + values, 2 layers, 2 heads of 4
    # three tokens attending 5, 6 and 7 keys
    assert F.decode(D, 3, 18) == 3 * (dense + head) + 18 * pair
    # prefill of 3 tokens: causal pairs 1 + 2 + 3, head once
    assert F.prefill(D, 3) == 3 * dense + 6 * pair + head
    assert F.prefill(D, 0) == 0


def test_prm_and_embed_counts():
    dense = 2 * 2 * 576
    pair = 4 * 2 * 2 * 4
    assert F.prm_score(D, 4) == 4 * dense + 10 * pair + 2 * 8 * 4
    assert F.embed(D, 4) == 4 * dense + 16 * pair


def test_tree_attention_bytes():
    # pages of 8 slots x 1 kv head x 4, f32: 128 bytes; k and v
    # for 5 unique pages, q and out for 3 rows of 2 heads x 4
    per_layer = 2 * 128 * 5 + 2 * 3 * 2 * 4 * 4
    assert F.tree_attention_bytes(D, 8, 5, 3) == 2 * per_layer


def test_dims_from_a_config_file():
    c = {"num_hidden_layers": 4, "hidden_size": 3072,
         "num_attention_heads": 32, "num_key_value_heads": 32,
         "intermediate_size": 8192, "vocab_size": 32064}
    d = F.Dims.of(c)
    assert d.head_dim == 96 and d.gated
    assert d.layer_params == 4 * 3072 * 3072 + 3 * 3072 * 8192
