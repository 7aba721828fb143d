"""Model families in the harness: the dense family maps, makes weights
and counts as the harness did before families existed, and a new
family joins the benchmark as new files and entries only."""
import dataclasses
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny as BT
from benchmarks.chip import flops as F
from benchmarks.chip import harness as H
from benchmarks.chip import traffic as TF
from benchmarks.chip import weights as W

dense = H.family(BT.REPO, "dense")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return BT.make_root(str(tmp_path_factory.mktemp("families_root")))


# ---------------------------------------------------------------------------
# The dense family is the harness as it was
# ---------------------------------------------------------------------------

def _mapping_before(c, name, *, encoder=False):
    """The harness's mapping before families, verbatim."""
    from repro.configs.base import ModelConfig
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


def _leaf_before(path, shape, key):
    """The weight rule before families, verbatim."""
    name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)
    last = name.rsplit("/", 1)[-1]
    if last.startswith("ln") or last.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if last == "embed":
        return z * 0.02
    return z * (1.0 / shape[-2] ** 0.5)


def _weights_before(models, seed, skip):
    """``weights.make`` before families, verbatim."""
    shapes = [jax.eval_shape(m.init, jax.random.key(0)) for m in models]
    shapes = [{k: v for k, v in t.items() if k not in s}
              for t, s in zip(shapes, skip)]

    def fill(key):
        out = []
        for i, tree in enumerate(shapes):
            leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
            keys = jax.random.split(jax.random.fold_in(key, i), len(leaves))
            out.append(jax.tree_util.tree_unflatten(treedef, [
                _leaf_before(path, leaf.shape, k)
                for (path, leaf), k in zip(leaves, keys)]))
        return out

    return jax.jit(fill)(jax.random.key(seed))


@pytest.mark.parametrize("name", ["phi3-w16-sweep", "yi6b-w4-open",
                                  "tiny-closed"])
def test_dense_mapping_is_unchanged(root, name):
    cell = H.load_cell(BT.REPO if name != "tiny-closed" else root, name)
    assert cell.family.__name__ == "bench_family_dense"
    c, n = cell.config, H.max_seq_len(cell)
    for got, want in (
            (cell.family.model_config(H.generator_config(c), "g", n),
             _mapping_before(H.generator_config(c), "g")),
            (cell.family.model_config(H.prm_config(c), "p", n),
             _mapping_before(H.prm_config(c), "p")),
            (dense.model_config(c["embedder"], "e", n, encoder=True),
             _mapping_before(c["embedder"], "e", encoder=True))):
        assert dataclasses.asdict(got) == dataclasses.asdict(want)


def test_dense_weights_are_the_rule_before_families(root):
    from repro.models.model import build_model
    cell = H.load_cell(root, "tiny-closed")
    c, n = cell.config, H.max_seq_len(cell)
    models = (
        build_model(dense.model_config(H.generator_config(c), "g", n)),
        build_model(dense.model_config(H.prm_config(c), "p", n),
                    with_value_head=True),
        build_model(dense.model_config(c["embedder"], "e", n, encoder=True)))
    seed = 2 ** 31 + 7
    got = H.make_weights(models, seed, cell.family)
    want = _weights_before(models, TF.sub_seed(seed, 1),
                           ((), ("lm_head",), ()))
    for g, w in zip(got, want):
        gl = jax.tree_util.tree_leaves_with_path(g)
        wl = jax.tree_util.tree_leaves_with_path(w)
        assert [p for p, _ in gl] == [p for p, _ in wl]
        for (path, a), (_, b) in zip(gl, wl):
            assert np.array_equal(np.asarray(a), np.asarray(b)), path


@pytest.mark.parametrize("key,value", [("tie_word_embeddings", True),
                                       ("sliding_window", 100)])
def test_dense_refuses_a_key_it_would_drop(key, value):
    c = dict(H.generator_config(BT.CONFIG), **{key: value})
    with pytest.raises(ValueError, match=key):
        dense.model_config(c, "tiny", 101)
    # a window no sequence of the cell outruns attends in full
    dense.model_config(dict(c, tie_word_embeddings=False), "tiny", 100)


class _Fake:
    def __init__(self, tree):
        self.tree = tree

    def init(self, key):
        return jax.tree.map(lambda s: jnp.zeros(s, jnp.float32), self.tree,
                            is_leaf=lambda s: isinstance(s, tuple))


@pytest.mark.parametrize("leaf,shape", [("groups/0/bias", (2, 3, 8)),
                                        ("bias", (8,))])
def test_uncovered_vector_leaf_is_refused(leaf, shape):
    """A leaf that is one vector a layer, and no norm, has no fan-in;
    the norm of its layer group shows its group's layer axes."""
    tree = {"groups": [{"ln": (2, 3, 8), "w": (2, 3, 8, 8)}],
            "embed": (16, 8)}
    if leaf.startswith("groups/"):
        tree["groups"][0]["bias"] = shape
    else:
        tree["bias"] = shape
    with pytest.raises(ValueError, match=leaf):
        W.make([_Fake(tree)], 1)

    def rule(name, shape, key):
        return jnp.full(shape, 0.5) if name == leaf else None
    out = W.make([_Fake(tree)], 1, rules=[rule])[0]
    assert float(out["groups"][0]["w"].std()) > 0.1
    assert np.all(np.asarray(out["bias"] if leaf == "bias"
                             else out["groups"][0]["bias"]) == 0.5)


def test_engine_object_reaches_engine_config(root):
    cell = H.load_cell(root, "tiny-closed")
    cell.config = dict(cell.config, engine=dict(cell.config["engine"],
                                                n_state_pages=24))
    e = H.engine_config(cell, use_kernel=False)
    assert (e.n_state_pages, e.n_pages, e.page_size) == (24, 64, 8)
    assert e.attention == "tree" and not e.use_kernel
    assert e.max_batch == cell.mix["max_live"] * cell.mix["width"]
    assert e.max_seq_len == H.max_seq_len(cell)
    # the mix sets the batch and length, the harness the timed path
    for k, v in (("max_batch", 32), ("max_seq_len", 32),
                 ("attention", "paged"), ("use_kernel", True),
                 ("prefill", "dense"), ("trace_logits", True),
                 ("mesh", None), ("prefill_chunk_tokens", 64)):
        cell.config = dict(cell.config, engine={"n_pages": 64,
                                                "page_size": 8, k: v})
        with pytest.raises(ValueError, match=k):
            H.engine_config(cell, use_kernel=False)


def test_recorded_operations_are_flops_py(root):
    cell, stack, rec = H.prepare(root, "tiny-closed", 2 ** 31 + 9,
                                 use_kernel=False)
    c = cell.config
    traffic = TF.generate(cell.mix, 2 ** 31 + 9, 1.0, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))
    H.run_window(cell, stack, traffic, 1.0, min_finished=1)
    g = F.Dims.of(H.generator_config(c))
    p = F.Dims.of(H.prm_config(c))
    e = F.Dims.of(c["embedder"])
    want = (F.decode(g, rec.decode_rows, rec.decode_keys_sum)
            + sum(F.prefill(g, n) for n in rec.prefill_ctx)
            + sum(F.prm_score(p, n) for _, n, _ in rec.score_rows)
            + sum(F.embed(e, len(t)) for t, _ in rec.embed_rows if t))
    assert rec.decode_rows and rec.prefill_ctx and rec.score_rows
    assert H.dims(cell) == {"generator": g, "prm": p, "embedder": e}
    assert rec.flops(H.dims(cell), cell.family) == want


# ---------------------------------------------------------------------------
# A family added as new files only
# ---------------------------------------------------------------------------

HYBRID_FAMILY = textwrap.dedent('''
    """A tiny Mamba2/attention hybrid for the CPU test that a family
    joins the benchmark as new files only.  Only the harness's plumbing
    is under test, so its "reference" is the program's own full forward
    pass: it is no plain reference, and no cell of the benchmark uses
    this family."""
    import functools

    import jax
    import jax.numpy as jnp

    from benchmarks.chip import flops as F

    CALLS = set()


    def _seen(fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            CALLS.add(fn.__name__)
            return fn(*a, **k)
        return wrapped


    @_seen
    def model_config(c, name, max_seq_len):
        from repro.configs.base import ModelConfig, SSMConfig
        return ModelConfig(
            name=name, arch_type="hybrid", n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            attn_every=c["attn_every"], shared_attn_params=True,
            ssm=SSMConfig(kind="mamba2", d_state=c["mamba_d_state"],
                          d_conv=c["mamba_d_conv"],
                          head_dim=c["mamba_headdim"],
                          expand=c["mamba_expand"],
                          chunk_size=c["chunk_size"]),
            norm_eps=c["rms_norm_eps"], dtype=c["torch_dtype"])


    @_seen
    def leaf_init(name, shape, key):
        last = name.rsplit("/", 1)[-1]
        if last == "A_log":         # decay rates in [1, 16]
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if last == "dt_bias":       # softplus(dt_bias) in [1e-3, 1e-1]
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if last in ("D", "norm_w"):
            return jnp.ones(shape, jnp.float32)
        if last == "conv_b":
            return jnp.zeros(shape, jnp.float32)
        return None


    def _model(c, **kw):
        from repro.models.model import build_model
        return build_model(model_config.__wrapped__(c, "ref", 0),
                           remat=False, **kw)


    @_seen
    def token_logits(params, c, tokens, at, keys, temperature, fp8=False):
        if fp8:
            raise NotImplementedError("no control for a test family")
        lg = _model(c).forward(params, {"tokens": tokens[None]})[0][0]
        lg = lg[at - 1].astype(jnp.float32)
        if temperature > 0:
            noise = jax.vmap(lambda k: jax.random.gumbel(
                k, (lg.shape[-1],), jnp.float32))(keys)
            lg = lg / temperature + noise
        return lg


    @_seen
    def prm_reward(params, c, tokens, length, fp8=False):
        if fp8:
            raise NotImplementedError("no control for a test family")
        model = _model(c, with_value_head=True)
        return model.reward(params, {"tokens": tokens[None]})[0, length - 1]


    @_seen
    def dims(c):
        return F.Dims.of(c)


    @_seen
    def prefill(d, n_ctx):
        return F.prefill(d, n_ctx)


    @_seen
    def decode(d, n_tokens, keys_sum):
        return F.decode(d, n_tokens, keys_sum)


    @_seen
    def prm_score(d, n):
        return F.prm_score(d, n)
''')

HYBRID = dict(
    BT.CONFIG, name="tiny-hybrid", family="tinyhybrid", hidden_size=32,
    intermediate_size=64, num_hidden_layers=2, attn_every=2,
    num_attention_heads=2, num_key_value_heads=2, mamba_d_state=8,
    mamba_d_conv=4, mamba_headdim=16, mamba_expand=2, chunk_size=16,
    engine={"n_pages": 64, "page_size": 8, "n_state_pages": 32})


def test_a_family_joins_as_new_files_only(tmp_path):
    root = BT.make_root(str(tmp_path))
    d = os.path.join(root, BT.DATA)
    with open(os.path.join(d, "families", "tinyhybrid.py"), "w") as f:
        f.write(HYBRID_FAMILY)
    for rel, obj in (("configs/tiny-hybrid.json", HYBRID),
                     ("limits/tiny-hybrid-closed.json", BT.LIMITS)):
        with open(os.path.join(d, rel), "w") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": f"{BT.DATA}/configs/tiny-hybrid.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-hybrid-closed",
                               "config": "tiny-hybrid", "traffic": "closed",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-closed" in m.get("workloads", []):
            m["workloads"].append("tiny-hybrid-closed")
    json.dump(bench, open(path, "w"))

    seed = 2 ** 31 + 23
    cell = H.load_cell(root, "tiny-hybrid-closed")
    stack = H.build(cell, seed, use_kernel=False)
    eng = stack.engine
    assert eng.state is not None and eng.state.n_pages == 32
    gen = stack.params[0]["groups"][0]["mamba"]
    assert float(gen["A_log"].min()) >= 0.0 and np.all(gen["D"] == 1.0)
    warmed = H.warm(cell, stack)
    assert warmed["state_swap"] == 6        # buckets 1, 2, ..., 32
    compiles = H.CompileCounter()
    compiles.armed = True                   # a demotion and restore of
    for n in (1, 3, 31):                    # any size is warmed
        pages = eng.state.alloc(n)
        host = eng.state.gather_pages_async(pages).resolve()
        eng.state.release(pages)
        pages = eng.state.alloc(n)
        eng.state.scatter_pages(pages, host)
        eng.state.release(pages)
    compiles.armed = False
    assert compiles.n == 0

    rec = H.Recorder(stack)
    c = cell.config
    traffic = TF.generate(cell.mix, seed, 1.0, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))

    def arm(on):
        compiles.armed = on
    win = H.run_window(cell, stack, traffic, 1.0, min_finished=2,
                       on_open=lambda: arm(True), on_close=lambda: arm(False))
    assert compiles.n == 0 and win.counters["n_decode_steps"] > 0
    readings = H.check(cell, stack, rec, win, seed)
    assert readings["token_gap"]["n"] > 0 and readings["prm_gap"]["n"] > 0
    assert H.judge(readings, cell.limits), readings
    assert rec.flops(H.dims(cell), cell.family) > 0
    assert cell.family.CALLS == {
        "model_config", "leaf_init", "token_logits", "prm_reward", "dims",
        "prefill", "decode", "prm_score"}
    # the dense cells beside it are as they were
    assert H.load_cell(root, "tiny-closed").family.__name__ == \
        "bench_family_dense"
