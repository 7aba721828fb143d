"""The chip benchmark's harness: build one cell's stack from its files,
warm every shape the cell's traffic uses, drive the program's serving
loop for a measured window, compare what it produced with the plain
reference, and reduce counters and the trace to metrics.

A cell is found by its name in ``BENCHMARK.json``; everything that
belongs to it sits in files of its own under ``benchmarks/chip``:
``configs/<config>.json`` (sizes, PRM, embedder, engine pool),
``traffic/<mix>.json`` (see ``traffic.py``), ``metrics/<metric>.py``
(one ``read(ctx)`` per per-layer metric) and ``limits/<cell>.json``
(the correctness limits, set from measured readings).

The window drives ``ServingLoop.tick()`` over one ``LMBackend`` on one
``PagedEngine`` with tree attention.  The harness owns the clock: the
loop's virtual clock is never read as time.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import statistics
import sys
import time
from collections import defaultdict
from typing import Any, Dict, List, Optional

import numpy as np

from . import flops as F
from . import reference as R
from . import trace as TR
from . import traffic as TF
from . import weights as W

DATA = os.path.join("benchmarks", "chip")


# ---------------------------------------------------------------------------
# Finding a cell's files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    root: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: dict


def _json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix, metrics and limits."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _json(os.path.join(root, cfg["file"]))
    mix = _json(os.path.join(root, DATA, "traffic", w["traffic"] + ".json"))

    def mine(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    lim_path = os.path.join(root, DATA, "limits", name + ".json")
    limits = _json(lim_path) if os.path.exists(lim_path) else {}
    return Cell(name, root, config, mix, int(w["chips"]), e2e, per_layer,
                limits)


def metric_reader(root: str, metric: str):
    """``read(ctx)`` of ``metrics/<metric>.py``."""
    path = os.path.join(root, DATA, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(root: str, device_kind: str) -> dict:
    table = _json(os.path.join(root, DATA, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json")
    return table[device_kind]


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def generator_config(config: dict) -> dict:
    return {k: v for k, v in config.items()
            if not isinstance(v, (dict, list))}


def prm_config(config: dict) -> dict:
    out = generator_config(config)
    out.update({k: v for k, v in config["prm"].items()
                if not isinstance(v, (dict, list))})
    return out


def model_config(c: dict, name: str, *, encoder: bool = False):
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


def max_seq_len(cell: Cell) -> int:
    m = cell.mix
    ps = cell.config["engine"]["page_size"]
    return (TF.prompt_range(m)[1] + (m["max_steps"] + 1) * m["step_tokens"]
            + ps)


def _no_answer(tokens):
    return None


@dataclasses.dataclass
class Stack:
    engine: Any
    backend: Any
    scfg: Any
    models: tuple
    params: tuple          # generator, PRM, embedder weights


def build(cell: Cell, seed: int, *, use_kernel: bool) -> Stack:
    from repro.core import ETSConfig, SearchConfig
    from repro.models.model import build_model
    from repro.serving.engine import EngineConfig, PagedEngine
    from repro.serving.search_backend import BackendConfig, LMBackend

    c, m = cell.config, cell.mix
    lm = build_model(model_config(generator_config(c), c["name"]),
                     remat=False)
    prm = build_model(model_config(prm_config(c), c["name"] + "-prm"),
                      with_value_head=True, remat=False)
    emb = build_model(model_config(c["embedder"], "embedder", encoder=True),
                      remat=False)
    params = make_weights((lm, prm, emb), seed)
    ecfg = EngineConfig(
        n_pages=c["engine"]["n_pages"], page_size=c["engine"]["page_size"],
        max_batch=m["max_live"] * m["width"], max_seq_len=max_seq_len(cell),
        attention="tree", use_kernel=use_kernel)
    engine = PagedEngine(lm, params[0], ecfg)
    backend = LMBackend(
        engine, prm, params[1], emb, params[2],
        BackendConfig(step_token=c["step_token"], eos_token=c["eos_token"],
                      max_step_tokens=m["step_tokens"],
                      max_depth=m["max_steps"],
                      temperature=float(m["temperature"])),
        answer_fn=_no_answer, seed=TF.sub_seed(seed, 2))
    scfg = SearchConfig(method="ets", width=m["width"],
                        max_steps=m["max_steps"], ets=ETSConfig(**m["ets"]))
    return Stack(engine, backend, scfg, (lm, prm, emb), params)


def make_weights(models, seed: int):
    """Generator, PRM and embedder weights from ``seed``.  The PRM is
    served for its reward only, so its output head is not made."""
    return tuple(W.make(models, TF.sub_seed(seed, 1),
                        skip=((), ("lm_head",), ())))


def reseed(stack: Stack, seed: int) -> None:
    """New weights and sampling chains from ``seed`` on the same
    compiled stack (calibration runs many seeds in one process)."""
    stack.engine.params = stack.backend.prm_params = None
    stack.backend.embed_params = stack.params = None
    gen, prm, emb = make_weights(stack.models, seed)
    stack.engine.params = gen
    stack.backend.prm_params = prm
    stack.backend.embed_params = emb
    stack.backend.seed = TF.sub_seed(seed, 2)
    stack.params = (gen, prm, emb)


# ---------------------------------------------------------------------------
# Warm-up: every shape the cell's traffic can use, enumerated
# ---------------------------------------------------------------------------

def _pow2s(lo: int, hi: int, floor: int = 8) -> List[int]:
    from repro.kvcache.pool import pow2_bucket
    out, b = [], pow2_bucket(lo, floor)
    top = pow2_bucket(hi, floor)
    while b <= top:
        out.append(b)
        b *= 2
    return out


def warm(cell: Cell, stack: Stack) -> Dict[str, int]:
    """Compile (or load from the cache) every program the window can
    call: decode page buckets, prefill and PRM and embedder buckets,
    swap buckets, and the small per-row programs of the decode stream
    at every row count.  Returns how many shapes of each were warmed.

    The large programs are called directly, through the engine's and
    backend's own jitted functions, so a program that renames or
    re-signs one fails here, in set-up, and never compiles inside the
    window."""
    done: Dict[str, int] = {}
    for part in (_warm_decode, _warm_prefill, _warm_score_embed,
                 _warm_swap, _warm_stream):
        t = time.perf_counter()
        done.update(part(cell, stack))
        print(f"warm {part.__name__[6:]}: {time.perf_counter() - t:.3f} s",
              file=sys.stderr, flush=True)
    eng = stack.engine
    eng.alloc.check_invariants()
    assert eng.alloc.used_pages == 0, eng.alloc.used_pages
    import jax
    jax.block_until_ready((eng.pool.k, eng.pool.v))
    return done


def _zeros(*shape):
    return np.zeros(shape, np.int32)


def _warm_decode(cell: Cell, stack: Stack) -> Dict[str, int]:
    """The tree decode program at every unique-page bucket."""
    eng = stack.engine
    B, P = eng.ecfg.max_batch, eng.ecfg.n_pages
    i32, n = _zeros, 0
    for N in _pow2s(1, P - 1):
        out = eng._tree_decode_fn(
            eng.params, eng._put_rows(i32(B)), eng._put_rows(i32(B)),
            eng._put_rows(np.full(B, eng.dump_page, np.int32)),
            eng._put_rows(i32(B)), eng._put_rows(np.zeros(B, bool)),
            eng._put_repl(np.full(N, eng.dump_page, np.int32)),
            eng._put_repl(np.zeros((N, B), np.int8)),
            eng._put_repl(i32(N)), eng._put_rows(i32(B)),
            eng.pool.k, eng.pool.v, eng._state_in())
        _, eng.pool.k, eng.pool.v, state = out
        eng._state_out(state)
        n += 1
    return {"decode": n}


def _warm_prefill(cell: Cell, stack: Stack) -> Dict[str, int]:
    """Prefill of up to ``max_live`` prompts at every length bucket."""
    eng, m = stack.engine, cell.mix
    lo, hi = TF.prompt_range(m)
    i32, n = _zeros, 0
    for rows in _pow2s(1, m["max_live"], 1):
        for T in _pow2s(lo - 1, hi - 1):
            out = eng._prefill_fn(
                eng.params, eng._put_rows(i32(rows, T)),
                eng._put_rows(np.full((rows, T), -1, np.int32)),
                eng._put_rows(np.full((rows, T), eng.dump_page, np.int32)),
                eng._put_rows(i32(rows, T)), eng._put_rows(i32(rows)),
                eng._put_rows(i32(rows)), eng.pool.k, eng.pool.v,
                eng._state_in())
            _, eng.pool.k, eng.pool.v, state = out
            eng._state_out(state)
            n += 1
    return {"prefill": n}


def _warm_score_embed(cell: Cell, stack: Stack) -> Dict[str, int]:
    """The PRM over every candidate of a tick at every length bucket,
    the embedder over their last steps."""
    import jax
    import jax.numpy as jnp
    be, m = stack.backend, cell.mix
    lo, hi = TF.prompt_range(m)
    steps, cap = m["max_steps"], m["step_tokens"]
    i32, done = _zeros, defaultdict(int)
    for rows in _pow2s(1, stack.engine.ecfg.max_batch, 1):
        for T in _pow2s(lo + 1, hi + steps * cap):
            pos = np.broadcast_to(np.arange(T, dtype=np.int32), (rows, T))
            jax.block_until_ready(be._score_batch_fn(
                be.prm_params, jnp.asarray(i32(rows, T)), jnp.asarray(pos),
                jnp.asarray(np.full(rows, T, np.int32))))
            done["score"] += 1
        for T in _pow2s(1, cap):
            pos = np.broadcast_to(np.arange(T, dtype=np.int32), (rows, T))
            jax.block_until_ready(be._embed_batch_fn(
                be.embed_params, jnp.asarray(i32(rows, T)),
                jnp.asarray(pos)))
            done["embed"] += 1
    return dict(done)


def _warm_swap(cell: Cell, stack: Stack) -> Dict[str, int]:
    """Page demotion and restore at every page bucket."""
    eng = stack.engine
    shape, n = eng.pool.k.shape, 0
    for pages in _pow2s(1, eng.ecfg.n_pages):
        eng.pool.gather_pages_async([eng.dump_page] * pages)
        host = np.zeros((shape[0], pages) + tuple(shape[2:]), np.float32)
        eng.pool.scatter_pages([eng.dump_page] * pages, host, host,
                               dump_page=eng.dump_page)
        n += 1
    return {"swap": n}


def _warm_stream(cell: Cell, stack: Stack) -> Dict[str, int]:
    """The decode stream's small programs at every row count: key
    derivation per branch count, row-key picks, seating k rows (their
    keys stacked and scattered), copy-on-write of k pages, one step."""
    import jax
    import jax.numpy as jnp
    eng, be, m = stack.engine, stack.backend, cell.mix
    B, ps = eng.ecfg.max_batch, eng.ecfg.page_size
    lo = TF.prompt_range(m)[0]
    # a prompt in the mix's range whose last page is shared part-full,
    # so every branch's first token copies it
    n = lo if (lo - 1) % ps else lo + 1
    prompt = [1] * n
    tree = be.start_many([prompt])[0]
    root = tree.node(0).payload["seq_id"]
    for n in range(1, m["width"] + 1):
        t = be.expand_begin(tree, [(0, n)])
        for r in range(n):
            t.row_keys[r]
        for bid in t.branches:
            eng.free(bid)
    stream = be.open_stream()
    keys = jax.random.split(jax.random.key(0), B)
    for k in range(1, B + 1):
        bids = eng.branch(root, k)
        stream.add(bids, jnp.stack([keys[r] for r in range(k)]), 1)
        stream.step()
        for bid in bids:
            eng.free(bid)
    be.finish_problem(tree)
    import scipy.cluster.hierarchy  # noqa: F401  (ETS imports these lazily)
    import scipy.optimize  # noqa: F401
    return {"stream_rows": B}


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

def span(name, fn):
    """``fn`` inside the host span ``bench.<name>``."""
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(TR.SPAN_PREFIX + name):
            return fn(*a, **k)
    return wrapped


class Recorder:
    """The harness's wrappers around the calls into each layer: host
    spans for the trace, and what the correctness check and the
    per-layer metrics read afterwards."""

    def __init__(self, stack: Stack):
        self.stack = stack
        self.reset()
        self._wrap()

    def reset(self):
        self.iters: List[tuple] = []        # (keys, slot_seq) per step
        self.kv_pages: List[int] = []
        self.decode_keys_sum = 0
        self.decode_rows = 0
        self.prefill_ctx: List[int] = []
        self.score_rows: List[tuple] = []   # (tokens, length, reward)
        self.embed_rows: List[tuple] = []   # (tokens, embedding)

    def _wrap(self):
        be, eng = self.stack.backend, self.stack.engine
        rec = self
        start_many, open_stream = be.start_many, be.open_stream
        score_multi, embed_multi = be.score_multi, be.embed_multi

        def start(prompts):
            rec.prefill_ctx += [len(p) - 1 for p in prompts]
            return start_many(prompts)

        def score(reqs):
            out = score_multi(reqs)
            for (tree, nodes), rs in zip(reqs, out):
                for n, r in zip(nodes, rs):
                    toks = eng.tokens[tree.node(n).payload["seq_id"]]
                    rec.score_rows.append((toks, len(toks), r))
            return out

        def embed(reqs):
            out = embed_multi(reqs)
            for (tree, nodes), es in zip(reqs, out):
                for n, e in zip(nodes, es):
                    rec.embed_rows.append((tree.node(n).payload["tokens"],
                                           e))
            return out

        def stream():
            s = open_stream()
            step = s.step

            def stepped():
                live = s.live
                rec.iters.append((s._keys, tuple(s._slot_seq)))
                rec.kv_pages.append(eng.alloc.used_pages)
                rec.decode_rows += len(live)
                rec.decode_keys_sum += sum(len(eng.tokens[i])
                                           for i in live)
                return step()
            s.step = span("decode_step", stepped)
            return s

        be.start_many = span("prefill", start)
        be.score_multi = span("score", score)
        be.embed_multi = span("embed", embed)
        be.open_stream = stream
        be.expand_begin = span("expand_begin", be.expand_begin)
        be.expand_finish = span("expand_finish", be.expand_finish)

    def flops(self, dims: Dict[str, F.Dims]) -> int:
        """Required operations of everything recorded."""
        g, p, e = dims["generator"], dims["prm"], dims["embedder"]
        total = F.decode(g, self.decode_rows, self.decode_keys_sum)
        total += sum(F.prefill(g, n) for n in self.prefill_ctx)
        total += sum(F.prm_score(p, n) for _, n, _ in self.score_rows)
        total += sum(F.embed(e, len(t)) for t, _ in self.embed_rows if t)
        return total


class CompileCounter:
    """Backend compilations (or compile-cache loads) while armed."""

    def __init__(self):
        import jax._src.monitoring as mon
        self.n = 0
        self.armed = False

        def listener(event, duration, **kw):
            if self.armed and event.endswith("backend_compile_duration"):
                self.n += 1
        mon.register_event_duration_secs_listener(listener)


def _engine_counters(eng, be) -> Dict[str, int]:
    return {k: int(getattr(obj, k)) for obj, keys in (
        (eng, ("n_decode_steps", "n_decoded_tokens", "n_prefill_tokens",
               "unique_pages_streamed", "logical_pages_streamed",
               "swapped_out_pages", "swapped_in_pages", "decode_traces",
               "prefill_traces")),
        (be, ("score_traces", "embed_traces"))) for k in keys}


@dataclasses.dataclass
class Window:
    window_s: float
    results: Dict[int, Any]
    steps_done: int
    attempted: int
    failed: int
    tta_s: List[float]
    queue_wait_s: List[float]
    late_s: List[float]         # how late each request was sent
    mid_step: int               # live problems partway through a step
    counters: Dict[str, int]
    prompts: Dict[int, List[int]]


CLOSE_CAP_S = 60.0


def between_steps(loop) -> bool:
    """No live problem of ``loop`` is partway through a search step."""
    return all(st.phase == "demand" for st in loop.live.values())


def run_window(cell: Cell, stack: Stack, traffic: TF.Traffic,
               seconds: float, *, on_open=None, on_close=None,
               min_finished: int = 0) -> Window:
    """Drive the serving loop for ``seconds`` of wall time.  Closed
    mixes keep ``max_live`` problems in flight from the backlog, and
    their window ends at a step boundary: once its time is up it runs
    on until no live problem is partway through a search step (at most
    ``CLOSE_CAP_S`` more), so every step counted is whole and all the
    time it took is in the window.  Open mixes submit each request once
    its due time has passed, and after the close keep serving
    (submitting nothing new) until every request sent is answered or
    ``drain_s`` has passed.  With ``min_finished`` a closed window runs
    on past ``seconds`` until that many problems have finished
    (calibration)."""
    import jax
    from repro.core import Request, ServingConfig, ServingLoop

    m, be, eng = cell.mix, stack.backend, stack.engine
    closed = m["mode"] == "closed"
    prompts = dict(enumerate(traffic.prompts))
    reqs = [Request(prompt=p, arrival=0.0) for p in traffic.prompts] \
        if closed else []
    loop = ServingLoop(be, stack.scfg, reqs, max_live=m["max_live"],
                       cfg=ServingConfig(refill=True))
    tick = span("tick", loop.tick)
    c0 = _engine_counters(eng, be)
    due: Dict[int, float] = {}
    late: List[float] = []
    admitted_at: Dict[int, float] = {}
    finished_at: Dict[int, float] = {}
    n_sub, n_adm, n_fin = 0, 0, 0

    def stamp(now):
        nonlocal n_adm, n_fin
        if len(loop.slo.admitted) != n_adm:
            for i in loop.slo.admitted:
                admitted_at.setdefault(i, now)
            n_adm = len(loop.slo.admitted)
        if len(loop.slo.finished) != n_fin:
            for i in loop.slo.finished:
                finished_at.setdefault(i, now)
            n_fin = len(loop.slo.finished)

    if on_open:
        on_open()
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        t0 = time.perf_counter()
        end = t0 + seconds
        while True:
            now = time.perf_counter()
            if not closed:
                while n_sub < len(traffic.due_s) and \
                        t0 + traffic.due_s[n_sub] <= now:
                    due[n_sub] = t0 + traffic.due_s[n_sub]
                    late.append(now - due[n_sub])
                    loop.submit(n_sub, Request(prompt=prompts[n_sub],
                                               arrival=0.0))
                    n_sub += 1
            if now >= end and (not closed or (
                    len(loop.results) >= min_finished
                    and (between_steps(loop)
                         or now >= end + CLOSE_CAP_S))):
                break
            more = tick()
            stamp(time.perf_counter())
            if not more and closed:
                break
            if not more:    # idle until the next request is due
                nxt = t0 + traffic.due_s[n_sub] \
                    if n_sub < len(traffic.due_s) else end
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        t1 = time.perf_counter()
        mid_step = sum(st.phase != "demand" for st in loop.live.values())
    if on_close:
        on_close()
    # progress inside the window, in steps (problems in flight at the
    # close count in part)
    steps_done = sum(
        (loop.live[i].steps if i in loop.live else
         loop.parked[i].steps if i in loop.parked else
         loop.results[i].steps if i in loop.results else 0)
        for i in admitted_at)
    c1 = _engine_counters(eng, be)
    if not closed:
        drain_end = t1 + float(m.get("drain_s", 60))
        while len(finished_at) < n_sub and time.perf_counter() < drain_end:
            more = tick()
            stamp(time.perf_counter())
            if not more:
                break
    tta, failed = [], 0
    if closed:
        attempted = len(admitted_at)
    else:
        attempted = n_sub
        for i, t in due.items():
            if i in finished_at:
                tta.append(finished_at[i] - t)
            else:
                failed += 1
                tta.append(time.perf_counter() - t)
    waits = [admitted_at[i] - due[i] for i in due if i in admitted_at]
    return Window(window_s=t1 - t0, results=dict(loop.results),
                  steps_done=steps_done, attempted=attempted, failed=failed,
                  tta_s=tta, queue_wait_s=waits, late_s=late,
                  mid_step=mid_step,
                  counters={k: c1[k] - c0[k] for k in c0},
                  prompts=prompts)


# ---------------------------------------------------------------------------
# Correctness: what the window produced against the plain reference
# ---------------------------------------------------------------------------

def _deepest_path(tree):
    """Node ids from the root's first child down to the deepest leaf."""
    leaf = max(range(len(tree)), key=lambda i: (tree.node(i).depth, -i))
    return tree.path(leaf)


def _pad(seq, T):
    out = np.zeros(T, np.int32)
    out[:len(seq)] = seq
    return out


def _bucket(n, q=128):
    return -(-n // q) * q


def check(cell: Cell, stack: Stack, rec: Recorder, win: Window, seed: int,
          *, control: bool = False) -> Dict[str, dict]:
    """Readings of the numbers compared, on a sample drawn from the
    seed: ``token_gap`` (widest gap by which a served token's noisy
    reference score lies below the best), ``prm_gap`` (widest |reward -
    reference reward|), ``embed_gap`` (widest max|e - e_ref| over
    max|e_ref|).  With ``control`` each also has the reading of the
    float8 reference put in the program's place."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(TF.sub_seed(seed, 4))
    want = cell.mix["check"]
    gen_c, prm_c = generator_config(cell.config), prm_config(cell.config)
    emb_c = cell.config["embedder"]
    gp, pp, ep = stack.params
    T = _bucket(max_seq_len(cell))
    temperature = float(cell.mix["temperature"])
    out: Dict[str, dict] = {}

    # served tokens of sampled finished requests, the longest among them
    done = sorted(win.results)
    served = {i: sum(win.results[i].tree.node(n).n_tokens
                     for n in _deepest_path(win.results[i].tree))
              for i in done}
    picks = []
    if done:
        longest = max(done, key=lambda i: (served[i], -i))
        rest = [i for i in done if i != longest]
        k = min(len(rest), want["requests"] - 1)
        picks = [longest] + sorted(rng.choice(rest, k, replace=False)
                                   .tolist() if k else [])
    paths = {}
    for i in picks:
        tree = win.results[i].tree
        paths[i] = [tree.node(n).payload for n in _deepest_path(tree)]
    targets = {pl["seq_id"] for p in paths.values() for pl in p}
    where = defaultdict(list)        # seq id -> [(iteration, slot)]
    for it, (_, slots) in enumerate(rec.iters):
        for j, sid in enumerate(slots):
            if sid in targets:
                where[sid].append((it, j))
    # each served token's sampling key: the key its row's chain held
    # at that iteration, split as the stream splits it
    key_rows: Dict[int, np.ndarray] = {}
    split = jax.jit(jax.vmap(lambda k: jax.random.split(k, 2)[1]))
    gaps, ctrl_gaps, n_tokens = [], [], 0
    n_at = cell.mix["max_steps"] * cell.mix["step_tokens"]
    for i in picks:
        seq = list(win.prompts[i])
        at, toks, kd = [], [], []
        for pl in paths[i]:
            for k, t in enumerate(pl["tokens"]):
                it, j = where[pl["seq_id"]][k]
                if it not in key_rows:
                    key_rows[it] = np.asarray(
                        jax.random.key_data(rec.iters[it][0]))
                at.append(len(seq))
                toks.append(t)
                kd.append(key_rows[it][j])
                seq.append(t)
        n = len(at)
        n_tokens += n
        at_p = np.full(n_at, at[0], np.int32)
        at_p[:n] = at
        kd += [kd[0]] * (n_at - n)
        keys = split(jax.random.wrap_key_data(jnp.asarray(np.stack(kd))))
        x, at_p = jnp.asarray(_pad(seq, T)), jnp.asarray(at_p)
        ref = np.asarray(R.token_logits(gp, gen_c, x, at_p, keys,
                                        temperature))[:n]
        best = ref.max(-1)
        gaps.append(float((best - ref[np.arange(n), toks]).max()))
        if control:
            low = np.asarray(R.token_logits(gp, gen_c, x, at_p, keys,
                                            temperature, fp8=True))[:n]
            ctrl_gaps.append(float((best - ref[np.arange(n),
                                               low.argmax(-1)]).max()))
    out["token_gap"] = {"value": max(gaps) if gaps else None,
                        "n": n_tokens, "requests": len(picks)}
    if control:
        out["token_gap"]["control"] = max(ctrl_gaps) if ctrl_gaps else None

    # PRM rewards of sampled scored rows, the longest among them
    rows = rec.score_rows
    if rows:
        longest = max(range(len(rows)), key=lambda r: rows[r][1])
        rest = [r for r in range(len(rows)) if r != longest]
        k = min(len(rest), want["prm_rows"] - 1)
        sel = [longest] + (rng.choice(rest, k, replace=False).tolist()
                           if k else [])
        g, cg = [], []
        for r in sel:
            toks, n, got = rows[r]
            x = jnp.asarray(_pad(toks[:n], T))
            ref = float(R.prm_reward(pp, prm_c, x, n))
            g.append(abs(got - ref))
            if control:
                cg.append(abs(float(R.prm_reward(pp, prm_c, x, n, fp8=True))
                              - ref))
        out["prm_gap"] = {"value": max(g), "n": len(sel)}
        if control:
            out["prm_gap"]["control"] = max(cg)
    else:
        out["prm_gap"] = {"value": None, "n": 0}

    # embeddings of sampled embedded rows, the longest among them
    rows = [r for r in rec.embed_rows if r[0]]
    if rows:
        longest = max(range(len(rows)), key=lambda r: len(rows[r][0]))
        rest = [r for r in range(len(rows)) if r != longest]
        k = min(len(rest), want["embed_rows"] - 1)
        sel = [longest] + (rng.choice(rest, k, replace=False).tolist()
                           if k else [])
        Te = _bucket(cell.mix["step_tokens"], 8)
        g, cg = [], []
        for r in sel:
            toks, got = rows[r]
            x = jnp.asarray(_pad(toks, Te))
            ref = np.asarray(R.embedding(ep, emb_c, x, len(toks)))
            scale = float(np.abs(ref).max())
            g.append(float(np.abs(np.asarray(got) - ref).max()) / scale)
            if control:
                low = np.asarray(R.embedding(ep, emb_c, x, len(toks),
                                             fp8=True))
                cg.append(float(np.abs(low - ref).max()) / scale)
        out["embed_gap"] = {"value": max(g), "n": len(sel)}
        if control:
            out["embed_gap"]["control"] = max(cg)
    else:
        out["embed_gap"] = {"value": None, "n": 0}
    return out


def judge(readings: Dict[str, dict], limits: Dict[str, float]) -> bool:
    """Correct when every number compared was read and is within its
    limit."""
    return bool(limits) and all(
        readings.get(k, {}).get("value") is not None
        and readings[k]["value"] <= lim for k, lim in limits.items())


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (0-100), linear between order statistics."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(cell: Cell, win: Window, setup_s: float) -> Dict[str, float]:
    vals = {
        "setup_s": setup_s,
        "problems_per_s": win.steps_done / cell.mix["max_steps"]
        / win.window_s,
        "tta_p50_s": percentile(win.tta_s, 50),
        "tta_p90_s": percentile(win.tta_s, 90),
    }
    return {m["name"]: vals[m["name"]] for m in cell.end_to_end
            if vals.get(m["name"]) is not None}


def dims(cell: Cell) -> Dict[str, F.Dims]:
    return {"generator": F.Dims.of(generator_config(cell.config)),
            "prm": F.Dims.of(prm_config(cell.config)),
            "embedder": F.Dims.of(cell.config["embedder"])}


def layer_context(cell: Cell, rec: Recorder, win: Window,
                  reduced: Optional[dict], peak: dict) -> dict:
    """What the per-layer readers read."""
    d = dims(cell)
    return {
        "cell": cell, "window_s": win.window_s, "counters": win.counters,
        "trace": reduced, "peak": peak, "dims": d,
        "page_size": cell.config["engine"]["page_size"],
        "problem_steps": win.steps_done, "queue_wait_s": win.queue_wait_s,
        "kv_pages": rec.kv_pages, "flops": rec.flops(d),
        "median": statistics.median,
    }


def prepare(root: str, name: str, seed: int, *, use_kernel: bool = True):
    """Cell, stack (warmed) and recorder for tools that drive several
    windows in one process (calibration, the rate sweep)."""
    cell = load_cell(root, name)
    stack = build(cell, seed, use_kernel=use_kernel)
    warm(cell, stack)
    return cell, stack, Recorder(stack)


def clear(stack: Stack) -> None:
    """Free every sequence and per-problem state a window left behind
    and zero the engine's counters, keeping the compiled programs."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        stack.backend.reset()
