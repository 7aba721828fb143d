"""The Zamba2 family through the harness at tiny widths: the plain
reference of ``families/zamba2.py`` against what the program served,
its float8 control, and its operation and byte counts against counts
made directly from the built model's shapes."""
import json
import os

import jax
import numpy as np
import pytest

import bench_tiny as BT
from benchmarks.chip import harness as H
from benchmarks.chip import traffic as TF

# 12 layers keep published hybrid layers 6 and 11: blocks A and B in turn
ZAMBA2 = dict(
    BT.CONFIG, name="tiny-zamba2", family="zamba2", hidden_size=32,
    num_hidden_layers=12, num_attention_heads=2, num_key_value_heads=2,
    attention_head_dim=32, attention_hidden_size=64, ffn_hidden_size=64,
    intermediate_size=64, hidden_act="gelu", adapter_rank=4,
    use_shared_mlp_adapter=True, use_shared_attention_adapter=False,
    use_mem_rope=True, use_long_context=False, add_bias_linear=False,
    use_conv_bias=True, tie_word_embeddings=True, num_mem_blocks=2,
    mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_headdim=16,
    n_mamba_heads=4, mamba_ngroups=2, chunk_size=16,
    prm={"num_hidden_layers": 12},
    engine={"n_pages": 64, "page_size": 8, "n_state_pages": 16})

CELL = "tiny-zamba2-closed"
SEED = 2 ** 31 + 41


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    root = BT.make_root(str(tmp_path_factory.mktemp("zamba2_root")))
    d = os.path.join(root, BT.DATA)
    for rel, obj in (("configs/tiny-zamba2.json", ZAMBA2),
                     (f"limits/{CELL}.json", BT.LIMITS)):
        with open(os.path.join(d, rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-zamba2", "source": "test",
                             "file": f"{BT.DATA}/configs/tiny-zamba2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-zamba2",
                               "traffic": "closed", "chips": 1,
                               "why": "test"})
    json.dump(bench, open(path, "w"))
    cell, stack, rec = H.prepare(root, CELL, SEED, use_kernel=False)
    c = cell.config
    traffic = TF.generate(cell.mix, SEED, 1.0, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))
    win = H.run_window(cell, stack, traffic, 1.0, min_finished=2)
    readings = H.check(cell, stack, rec, win, SEED, control=True)
    return cell, stack, rec, win, readings


def test_served_hybrid_matches_the_plain_reference(run):
    cell, stack, _, win, readings = run
    assert cell.family.__name__ == "bench_family_zamba2"
    eng = stack.engine
    assert eng.state is not None and eng.state_pages_copied > 0
    assert stack.params[0]["shared"]["ln_in"].shape == (2, 64)
    assert win.counters["n_decode_steps"] > 0
    assert readings["token_gap"]["n"] > 0 and readings["prm_gap"]["n"] > 0
    assert H.judge(readings, cell.limits), readings


def test_float8_control_is_not_correct(run):
    cell, _, _, _, readings = run
    control = {k: dict(r, value=r["control"]) for k, r in readings.items()}
    assert not H.judge(control, cell.limits), control


def _matrices(tree):
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


def test_operation_counts_are_a_direct_count(run):
    """Per token: every matrix of every Mamba2 layer, the depthwise
    conv, the recurrence's two contractions, and at each hybrid layer
    its block's matrices, its adapter and its linear; attention per
    query-key pair; the tied head (decode, prefill) or value head."""
    cell, stack, _, _, _ = run
    fam, c = cell.family, H.generator_config(cell.config)
    g = jax.eval_shape(stack.models[0].init, jax.random.key(0))
    mamba = g["groups"][0]["mamba"]
    n_h, shared = 2, g["shared"]
    per_token = (
        2 * _matrices([mamba[k] for k in ("z_proj", "xbc_proj", "dt_proj",
                                          "out_proj")])
        + 2 * _matrices(mamba["conv_w"])
        + 12 * 4 * 4 * 16 * 8               # 12 layers, 4 heads of 16 x 8
        # hybrid layers 6 and 11 use blocks A and B once each
        + 2 * _matrices([shared["attn"], shared["mlp"]])
        + 2 * _matrices(g["hybrid"]))
    pair = 4 * n_h * 2 * 32
    head = 2 * 32 * 256
    d = fam.dims(c)
    assert fam.prefill(d, 10) == per_token * 10 + pair * 55 + head
    assert fam.decode(d, 3, 40) == (per_token + head) * 3 + pair * 40
    p = fam.dims(H.prm_config(cell.config))
    assert fam.prm_score(p, 10) == per_token * 10 + pair * 55 + 2 * 32 * 10
    # tree_attn_roofline reads the attention over the pages alone
    assert (d.layers, d.heads, d.kv_heads, d.head_dim) == (n_h, 2, 2, 32)


def test_decode_bytes_is_the_hand_sum(run):
    cell, stack, _, _, _ = run
    fam, eng = cell.family, stack.engine
    d = fam.dims(H.generator_config(cell.config))
    weights = _matrices(jax.eval_shape(stack.models[0].init,
                                       jax.random.key(0))) * 4
    state = sum(a[:, 0].nbytes for a in eng.state.arrays.values())
    page = 8 * 2 * 32 * 4                      # page of one layer's K or V
    assert fam.decode_bytes(d, 8, 5, 7, 11) == \
        weights * 5 + 2 * state * 7 + 2 * 2 * page * 11


def test_published_layout_is_the_configuration_file():
    """The family holds the published lists the harness cannot pass it;
    the configuration file states them, and they agree."""
    fam = H.family(BT.REPO, "zamba2")
    c = json.load(open(os.path.join(BT.REPO, BT.DATA, "configs",
                                    "zamba2-7b.json")))
    assert tuple(c["hybrid_layer_ids"]) == fam.HYBRID_LAYER_IDS
    assert tuple(c["layers_block_type"]) == fam.LAYERS_BLOCK_TYPE
    assert fam.hybrid_ids(c) == (6,)
    m = fam.model_config(H.generator_config(c), "g", 552)
    assert (m.n_layers, m.hybrid_ids(), m.n_mem_blocks) == (7, (6,), 1)
    assert m.param_count() == fam.dims(H.generator_config(c)).weight_params()
