"""The serving stack's own host spans and counters: a profiled run emits
every span of ``repro.obs.SPANS`` nested as the layers nest, the PRM
counters follow the bucket arithmetic, and the loop's host stamps are
ordered."""
import dataclasses
import gc
import glob
import os

import jax
import pytest

from repro import obs
from repro.configs import get_config
from repro.configs.base import ModelConfig, SSMConfig
from repro.core import (ETSConfig, Request, SearchConfig, ServingConfig,
                        ServingLoop)
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, PagedEngine
from repro.serving.search_backend import BackendConfig, LMBackend


@pytest.fixture(scope="module")
def tiny_models():
    lm_cfg = dataclasses.replace(get_config("tiny-lm"), n_layers=1,
                                 d_model=64, n_heads=4, n_kv_heads=2,
                                 d_ff=128)
    lm = build_model(lm_cfg, remat=False)
    prm = build_model(lm_cfg, with_value_head=True, remat=False)
    emb_cfg = dataclasses.replace(get_config("tiny-embedder"), n_layers=1,
                                  d_model=64, n_heads=2, n_kv_heads=2,
                                  d_ff=128)
    emb = build_model(emb_cfg, remat=False)
    return ((lm, lm.init(jax.random.key(0))),
            (prm, prm.init(jax.random.key(1))),
            (emb, emb.init(jax.random.key(2))))


def _backend(tiny_models, generator=None):
    (lm, lm_p), (prm, prm_p), (emb, emb_p) = tiny_models
    if generator is not None:
        lm, lm_p = generator
    engine = PagedEngine(lm, lm_p, EngineConfig(
        n_pages=256, page_size=8, max_batch=16, max_seq_len=128,
        attention="tree"))
    return LMBackend(engine, prm, prm_p, emb, emb_p,
                     BackendConfig(step_token=2, eos_token=3,
                                   max_step_tokens=6, max_depth=3),
                     answer_fn=lambda full: None, seed=5)


PROMPTS = [list(range(4, 4 + n)) for n in (17, 23, 9)]
SCFG = SearchConfig(method="ets", width=4, max_steps=3,
                    ets=ETSConfig(lambda_b=1.0, lambda_d=1.0,
                                  cluster_threshold=0.2))

# the spans each span may open inside (None: at the top of the thread)
DECODE = {"engine.decode"}
PARENTS = {
    "loop.tick": {None},
    "loop.admit": {"loop.tick"},
    "loop.pressure": {"loop.tick"},
    "loop.seat": {"loop.tick"},
    "loop.retire": {"loop.tick", "loop.seat", "loop.pressure"},
    "search.select": {"loop.tick"},
    "ets.cluster": {"search.select"},
    "ets.ilp": {"search.select"},
    "backend.release": {"search.select", "loop.tick"},
    "backend.prefill": {"loop.admit"},
    "backend.expand_begin": {"loop.seat"},
    "backend.expand_finish": {"loop.tick"},
    "backend.score": {"loop.tick"},
    "backend.embed": {"loop.tick"},
    "engine.decode": {"loop.tick"},
    "engine.decode.reserve": DECODE,
    "engine.decode.metadata": DECODE,
    "engine.decode.launch": DECODE,
    "engine.decode.wait": DECODE,
    "engine.decode.commit": DECODE,
    "engine.state.copy": {"backend.expand_begin"},
}
# spans only a generator that keeps recurrent state opens
STATE_SPANS = {"engine.state.copy"}


def _program_spans(trace_dir):
    """``repro.*`` host events of the trace, per thread, as
    ``(start, end, name)``."""
    pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(pb[0]).planes:
        for line in plane.lines:
            spans = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                      ev.name[len(obs.PREFIX):]) for ev in line.events
                     if ev.name.startswith(obs.PREFIX)]
            if spans:
                out.append(spans)
    return out


def _parents(spans):
    """(name, innermost enclosing span's name or None) per span."""
    out, stack = [], []
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            stack.pop()
        out.append((name, stack[-1][2] if stack else None))
        stack.append((a, b, name))
    return out


def _profiled_run(backend, trace_dir):
    """A profiled serving run; returns (loop, the spans seen)."""
    loop = ServingLoop(backend, SCFG, [Request(prompt=p) for p in PROMPTS],
                       max_live=2, cfg=ServingConfig(refill=True))
    loop.submit(len(PROMPTS), Request(prompt=PROMPTS[0]))
    with jax.profiler.trace(trace_dir), obs.gc_spans():
        while loop.tick():
            pass
        gc.collect()
    seen = set()
    for spans in _program_spans(trace_dir):
        for name, parent in _parents(spans):
            seen.add(name)
            if name != "runtime.gc":
                assert parent in PARENTS[name], (name, parent)
    return loop, seen


def test_profiled_serving_run_emits_every_span_nested(tiny_models,
                                                      tmp_path):
    backend = _backend(tiny_models)
    loop, seen = _profiled_run(backend, str(tmp_path))
    assert seen == set(obs.SPANS) - STATE_SPANS
    # the loop's host stamps, beside its virtual clock
    slo = loop.slo
    assert set(slo.finished_wall) == set(range(len(PROMPTS) + 1))
    for i in slo.finished_wall:
        assert (slo.submitted_wall[i] <= slo.admitted_wall[i]
                <= slo.finished_wall[i])


def test_gc_spans_removes_its_hook():
    before = list(gc.callbacks)
    with obs.gc_spans():
        assert len(gc.callbacks) == len(before) + 1
        with obs.span("loop.tick"):
            gc.collect()
    assert gc.callbacks == before


def test_prm_counters_follow_the_bucket_arithmetic(tiny_models):
    backend = _backend(tiny_models)
    trees = backend.start_many(PROMPTS)
    backend.score_multi([(t, [0]) for t in trees])
    # 3 rows of 17, 23 and 9 tokens pad to a bucket of 4 rows x 32
    assert backend.n_scored_rows == 3
    assert backend.n_scored_tokens == 17 + 23 + 9
    assert backend.n_scored_padded_tokens == 4 * 32
    backend.score_multi([(trees[2], [0])])
    assert backend.n_scored_rows == 4
    assert backend.n_scored_tokens == 17 + 23 + 9 + 9
    assert backend.n_scored_padded_tokens == 4 * 32 + 1 * 16


def test_state_copy_span_and_counters(tiny_models, tmp_path):
    """A generator with recurrent state (a Mamba2/attention hybrid)
    opens every span, copy-on-branch among them, and counts the state
    pages it copied and the most it held at once."""
    cfg = ModelConfig(
        name="obs-hybrid", arch_type="hybrid", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64, attn_every=2,
        shared_attn_params=True, dtype="float32",
        ssm=SSMConfig(kind="mamba2", d_state=8, d_conv=4, head_dim=16,
                      expand=2, chunk_size=16))
    lm = build_model(cfg, remat=False)
    backend = _backend(tiny_models, (lm, lm.init(jax.random.key(3))))
    eng = backend.engine
    branched = []
    branch = eng.branch
    eng.branch = lambda sid, n: branched.append(n) or branch(sid, n)
    _, seen = _profiled_run(backend, str(tmp_path))
    assert seen == set(obs.SPANS)
    assert eng.state_pages_copied == sum(branched) > 0
    # two live problems of at most 4 kept leaves and 4 children each
    assert 4 < eng.state_pages_peak <= 2 * 2 * SCFG.width
    assert eng.state.n_free == eng.state.dump_page
    eng.reset_counters()
    assert eng.state_pages_copied == eng.state_pages_peak == 0
