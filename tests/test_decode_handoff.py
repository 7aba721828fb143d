"""Host-to-device hand-off of one decode iteration.

Contracts of the engine's step launch:

  * without a mesh, ``_put_rows`` / ``_put_repl`` hand the host arrays
    to the jitted programs as they are, and a program reached with
    numpy operands is the one reached with ``jnp.asarray`` operands (no
    second compile, no second trace) — so a warm-up through either form
    covers the serving window;
  * ``n_host_puts`` counts the Python-level transfers: none per
    iteration on one device (the mesh side is in ``test_mesh.py``);
  * ``_advance_keys`` is ``jax.random.split(k, 2)`` per row, bit for
    bit, and a temperature-1 stream samples what an explicit per-row
    key-chain loop samples;
  * sampling still goes through ``sampler.sample_tokens_rowwise``,
    looked up at call time.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.serving import sampler
from repro.serving.engine import EngineConfig, PagedEngine, _advance_keys

PROMPTS = [list(range(4, 4 + n)) for n in (11, 7, 15)]


@pytest.fixture(scope="module")
def tiny_lm():
    cfg = dataclasses.replace(get_config("tiny-lm"), n_layers=2,
                              d_model=64, n_heads=4, n_kv_heads=2,
                              d_ff=128)
    lm = build_model(cfg, remat=False)
    return lm, lm.init(jax.random.key(0))


def _engine(tiny_lm, attention="tree", **kw):
    lm, params = tiny_lm
    return PagedEngine(lm, params, EngineConfig(
        n_pages=64, page_size=8, max_batch=8, max_seq_len=64,
        attention=attention, **kw))


@pytest.fixture
def backend_compiles():
    """Counts backend compilations while the test runs."""
    import jax._src.monitoring as mon
    seen = []

    def listener(event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            seen.append(event)
    mon.register_event_duration_secs_listener(listener)
    yield seen
    mon.unregister_event_duration_listener(listener)


def _tree_args(eng):
    B, N, i32 = eng.ecfg.max_batch, 8, lambda *s: np.zeros(s, np.int32)
    return "decode_traces", eng._tree_decode_fn, [
        (i32(B), "rows"), (i32(B), "rows"),
        (np.full(B, eng.dump_page, np.int32), "rows"), (i32(B), "rows"),
        (np.zeros(B, bool), "rows"),
        (np.full(N, eng.dump_page, np.int32), "repl"),
        (np.zeros((N, B), np.int8), "repl"), (i32(N), "repl"),
        (i32(B), "rows")]


def _paged_args(eng):
    B, T = eng.ecfg.max_batch, eng.max_pages_per_seq
    i32 = lambda *s: np.zeros(s, np.int32)
    return "decode_traces", eng._decode_fn, [
        (i32(B), "rows"), (np.full((B, T), -1, np.int32), "repl"),
        (i32(B), "rows"), (np.full(B, eng.dump_page, np.int32), "rows"),
        (i32(B), "rows"), (np.zeros(B, bool), "rows"), (i32(B), "rows")]


def _prefill_args(eng):
    rows, T, i32 = 2, 16, lambda *s: np.zeros(s, np.int32)
    return "prefill_traces", eng._prefill_fn, [
        (i32(rows, T), "rows"), (np.full((rows, T), -1, np.int32), "rows"),
        (np.full((rows, T), eng.dump_page, np.int32), "rows"),
        (i32(rows, T), "rows"), (i32(rows), "rows"), (i32(rows), "rows")]


def _call(eng, fn, ops, form):
    put = {"rows": eng._put_rows, "repl": eng._put_repl}
    args = [put[kind](a) if form == "put" else jnp.asarray(a)
            for a, kind in ops]
    _, eng.pool.k, eng.pool.v, state = fn(
        eng.params, *args, eng.pool.k, eng.pool.v, eng._state_in())
    eng._state_out(state)
    jax.block_until_ready(eng.pool.k)


@pytest.mark.parametrize("first", ["put", "asarray"])
@pytest.mark.parametrize("program", ["tree", "paged", "prefill"])
def test_operand_forms_reach_one_executable(tiny_lm, backend_compiles,
                                            program, first):
    eng = _engine(tiny_lm, "paged" if program == "paged" else "tree")
    counter, fn, ops = {"tree": _tree_args, "paged": _paged_args,
                        "prefill": _prefill_args}[program](eng)
    second = "asarray" if first == "put" else "put"
    _call(eng, fn, ops, first)
    traces, compiles = getattr(eng, counter), len(backend_compiles)
    assert traces == 1 and compiles >= 1
    _call(eng, fn, ops, second)
    assert getattr(eng, counter) == traces
    assert len(backend_compiles) == compiles, backend_compiles
    assert eng.n_host_puts == 0


def test_no_host_puts_without_mesh(tiny_lm):
    """One device: the helpers return the host array itself, and no
    iteration makes a Python-level transfer."""
    eng = _engine(tiny_lm)
    a = np.arange(8, dtype=np.int32)
    assert eng._put_rows(a) is a and eng._put_repl(a) is a
    roots = eng.prefill_many(PROMPTS)
    kids = [k for r in roots for k in eng.branch(r, 2)]
    eng.decode(kids, 4, key=jax.random.key(3))
    assert eng.n_decode_steps == 4
    assert eng.n_host_puts == 0


def test_advance_keys_is_split_per_row():
    keys = jax.random.split(jax.random.key(2 ** 31 + 5), 12)
    nxt, sub = _advance_keys(keys)
    want = np.stack([np.asarray(jax.random.key_data(jax.random.split(k, 2)))
                     for k in keys])
    assert np.array_equal(np.asarray(jax.random.key_data(nxt)), want[:, 0])
    assert np.array_equal(np.asarray(jax.random.key_data(sub)), want[:, 1])


def _stream_run(eng, temperature=1.0):
    """Two rows seated at iteration 0, two more at iteration 2 with
    other budgets: returns {seq: tokens}, the row keys and each
    iteration's slot layout."""
    roots = eng.prefill_many(PROMPTS[:2])
    kids = [k for r in roots for k in eng.branch(r, 2)]
    keys = jax.random.split(jax.random.key(11), len(kids))
    eng.logits_trace.clear()      # keep the decode iterations' alone
    stream = eng.open_stream(temperature=temperature)
    stream.add(kids[:2], keys[:2], 5)
    layouts = []
    while stream.live:
        if len(layouts) == 2:
            stream.add(kids[2:], keys[2:], 3)
        layouts.append(tuple(stream._slot_seq))
        stream.step()
    return {i: stream.out[i] for i in kids}, dict(zip(kids, keys)), layouts


def test_stream_matches_explicit_key_chain(tiny_lm):
    """Each row's token at each live iteration is the categorical draw
    of the second half of its chain's split; the first half carries the
    chain on (a temperature-1 stream with a refill mid-flight)."""
    eng = _engine(tiny_lm, trace_logits=True)
    out, keys, layouts = _stream_run(eng)
    chain = dict(keys)
    want = {i: [] for i in keys}
    for it, slots in enumerate(layouts):
        logits = eng.logits_trace[it]
        for j, i in enumerate(slots):
            if i is None:
                continue
            chain[i], sub = jax.random.split(chain[i], 2)
            want[i].append(int(jax.random.categorical(
                sub, jnp.asarray(logits[j], jnp.float32))))
    assert out == want
    assert all(len(t) == n for t, n in zip(out.values(), (5, 5, 3, 3)))


def test_patched_rowwise_sampler_changes_tokens(tiny_lm, monkeypatch):
    want, _, _ = _stream_run(_engine(tiny_lm))
    orig = sampler.sample_tokens_rowwise

    def shifted(keys, logits, temperature=1.0):
        return (orig(keys, logits, temperature) + 1) % logits.shape[-1]
    monkeypatch.setattr(sampler, "sample_tokens_rowwise", shifted)
    eng = _engine(tiny_lm)
    got, _, _ = _stream_run(eng)
    # every row's first draw sees the parent's logits and key
    V = eng.cfg.vocab_size
    assert all(got[i][0] == (want[i][0] + 1) % V for i in want)
