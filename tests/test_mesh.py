"""Mesh-aware engine + replica scaling.

Equivalence contracts of the distributed serving layer:

  * a 1-device mesh engine is bit-identical to the historical mesh-less
    engine in BOTH attention modes (the mesh only changes placement,
    never bits — the oracle every multi-device layout is built on);
  * a multi-replica sweep / serving loop is bit-identical per problem
    to serial single-replica runs, whatever the routing (per-problem
    RNG namespaces are seeded from the backend seed alone, so which
    replica runs a problem is invisible to its streams) —
    property-tested over random routers and arrival patterns;
  * on four devices, a ``model=4`` mesh engine decodes what the
    mesh-less engine decodes, and ``replica_meshes`` gives each replica
    its own devices;
  * ``make_host_mesh`` rejects non-divisible model-axis sizes up front;
  * the Pallas wrapper seam refuses multi-device meshes (the kernels
    are per-device until wrapped in shard_map).
"""
import dataclasses

import jax
import numpy as np
import pytest
from _hypothesis_shim import HealthCheck, given, settings, st
from test_serving import (StubBackend, STUB_PROMPTS, STUB_SCFG,
                          _assert_results_identical)

from repro.configs import get_config
from repro.core import (ETSConfig, ReplicaServingLoop, ReplicaSweep,
                        Request, SearchConfig, ServingConfig, ServingLoop,
                        run_search, run_search_many)
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, PagedEngine
from repro.serving.search_backend import BackendConfig, LMBackend


# ---------------------------------------------------------------------------
# make_host_mesh: divisibility guard + model=1 fast path
# ---------------------------------------------------------------------------

def test_make_host_mesh_model1_fast_path():
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["model"] == 1
    assert mesh.shape["data"] == jax.device_count()


def test_make_host_mesh_rejects_nondivisible_model():
    # this suite runs on 1 device, so any model > 1 cannot divide it
    bad = jax.device_count() + 1
    with pytest.raises(ValueError, match="must be >= 1 and divide"):
        make_host_mesh(model=bad)
    with pytest.raises(ValueError, match="must be >= 1 and divide"):
        make_host_mesh(model=0)


# ---------------------------------------------------------------------------
# Kernel wrapper seam: multi-device mesh + Pallas path is refused
# ---------------------------------------------------------------------------

def test_check_mesh_compat_guards_kernel_path():
    from repro.kernels.ops import check_mesh_compat

    class FakeBigMesh:
        size = 4

    check_mesh_compat(None, use_kernel=True)             # no mesh: fine
    check_mesh_compat(FakeBigMesh(), use_kernel=False)   # jnp path: fine
    check_mesh_compat(make_host_mesh(), use_kernel=True)  # 1 device: fine
    with pytest.raises(ValueError, match="shard_map"):
        check_mesh_compat(FakeBigMesh(), use_kernel=True)


# ---------------------------------------------------------------------------
# 1-device mesh == mesh-less engine, both attention modes (LM backend)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_models():
    lm_cfg = dataclasses.replace(get_config("tiny-lm"), n_layers=2,
                                 d_model=64, n_heads=4, n_kv_heads=2,
                                 d_ff=128)
    lm = build_model(lm_cfg, remat=False)
    lm_params = lm.init(jax.random.key(0))
    prm = build_model(dataclasses.replace(lm_cfg, n_layers=1),
                      with_value_head=True, remat=False)
    prm_params = prm.init(jax.random.key(1))
    emb_cfg = dataclasses.replace(get_config("tiny-embedder"), n_layers=1,
                                  d_model=64, n_heads=2, n_kv_heads=2,
                                  d_ff=128)
    emb = build_model(emb_cfg, remat=False)
    emb_params = emb.init(jax.random.key(2))
    return (lm, lm_params), (prm, prm_params), (emb, emb_params)


def _lm_backend(tiny_models, attention, mesh=None):
    (lm, lm_params), (prm, prm_params), (emb, emb_params) = tiny_models
    engine = PagedEngine(lm, lm_params, EngineConfig(
        n_pages=256, page_size=8, max_batch=32, max_seq_len=128,
        attention=attention, mesh=mesh))
    backend = LMBackend(engine, prm, prm_params, emb, emb_params,
                        BackendConfig(step_token=2, eos_token=3,
                                      max_step_tokens=6, max_depth=4),
                        answer_fn=lambda full: None, seed=13)
    return engine, backend


LM_PROMPTS = [list(range(4, 4 + n)) for n in (17, 23, 9)]
LM_SCFG = SearchConfig(method="ets", width=4, max_steps=2,
                       ets=ETSConfig(lambda_b=1.0, lambda_d=1.0,
                                     cluster_threshold=0.2))


@pytest.mark.parametrize("attention", ["tree", "paged"])
def test_one_device_mesh_bit_identical(tiny_models, attention):
    _, base = _lm_backend(tiny_models, attention)
    want = run_search_many(base, LM_SCFG, LM_PROMPTS)
    engine, backend = _lm_backend(tiny_models, attention,
                                  mesh=make_host_mesh())
    got = run_search_many(backend, LM_SCFG, LM_PROMPTS)
    _assert_results_identical(want, got)
    # the pool actually lives on the mesh, and on a 1-device mesh no
    # sharding rule can fall back
    assert engine.pool.sharding is not None
    assert engine.pool.k.sharding.mesh.size == 1
    assert engine.shard_fallbacks == []
    # the mesh engine puts each operand itself; the mesh-less one none
    assert engine.n_host_puts > 0
    assert base.engine.n_host_puts == 0


def test_replica_sweep_lm_bit_identical(tiny_models):
    """Two LM engine replicas behind one queue reproduce the
    single-backend sweep per problem (identically-seeded backends)."""
    _, base = _lm_backend(tiny_models, "tree")
    want = run_search_many(base, LM_SCFG, LM_PROMPTS)
    backends = [_lm_backend(tiny_models, "tree")[1] for _ in range(2)]
    got = run_search_many(backends, LM_SCFG, LM_PROMPTS)
    _assert_results_identical(want, got)


# ---------------------------------------------------------------------------
# Replica sweep: routing-invariant per-problem results (stub backend)
# ---------------------------------------------------------------------------

def _stub_serial(prompts, scfg=STUB_SCFG):
    be = StubBackend()
    return [run_search(be, scfg, tree=be.start(p)) for p in prompts]


def test_replica_sweep_matches_serial_runs():
    want = _stub_serial(STUB_PROMPTS)
    for n_rep in (1, 2, 3):
        rs = ReplicaSweep([StubBackend() for _ in range(n_rep)],
                          STUB_SCFG, STUB_PROMPTS)
        got = rs.run()
        _assert_results_identical(want, got)
        # every problem landed somewhere, none landed twice
        counts = [len(rep.sched.results) for rep in rs.replicas]
        assert sum(counts) == len(STUB_PROMPTS)
        if n_rep > 1:
            assert max(counts) < len(STUB_PROMPTS)   # routing spread


def test_run_search_many_unwraps_single_backend_list():
    want = run_search_many(StubBackend(), STUB_SCFG, STUB_PROMPTS)
    got = run_search_many([StubBackend()], STUB_SCFG, STUB_PROMPTS)
    _assert_results_identical(want, got)


@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.integers(0, 10 ** 6),       # router seed
       st.integers(1, 4),             # replicas
       st.integers(1, 5))             # per-replica max_live
def test_replica_sweep_random_routing_invariance(seed, n_rep, max_live):
    """ANY room-respecting router yields the same per-problem results:
    placement and admission order only move where/when a problem runs,
    never what it computes."""
    rng = np.random.default_rng(seed)

    def chaotic_router(eligible, loads):
        return eligible[int(rng.integers(len(eligible)))]

    want = _stub_serial(STUB_PROMPTS)
    rs = ReplicaSweep([StubBackend() for _ in range(n_rep)], STUB_SCFG,
                      STUB_PROMPTS, max_live=max_live,
                      router=chaotic_router)
    _assert_results_identical(want, rs.run())


# ---------------------------------------------------------------------------
# Replica serving loop: one arrival stream over N loops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("refill", [False, True])
def test_replica_serving_degenerate_trace(refill):
    """All arrivals at t=0: the replica pool reproduces the batch sweep
    per request, and the merged SLO report covers every request."""
    want = run_search_many(StubBackend(), STUB_SCFG, STUB_PROMPTS)
    pool = ReplicaServingLoop(
        [StubBackend() for _ in range(2)], STUB_SCFG,
        [Request(prompt=p) for p in STUB_PROMPTS],
        cfg=ServingConfig(refill=refill))
    _assert_results_identical(want, pool.run())
    rep = pool.slo.report()
    assert rep["n_finished"] == len(STUB_PROMPTS)
    assert sorted(pool.routed) == list(range(len(STUB_PROMPTS)))
    assert pool.clock == max(lp.clock for lp in pool.loops)


@settings(max_examples=10, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
@given(st.lists(st.tuples(st.integers(0, 50),     # arrival time
                          st.integers(0, 2)),     # priority class
                min_size=2, max_size=6),
       st.integers(1, 3),                         # replicas
       st.integers(0, 10 ** 6))                   # router seed
def test_replica_serving_timed_workload_invariance(specs, n_rep, seed):
    """Random arrivals, priorities, replica counts, and routers: every
    request finishes with its solo-run result — same contract the
    single serving loop holds, now fleet-wide."""
    rng = np.random.default_rng(seed)

    def chaotic_router(eligible, loads):
        return eligible[int(rng.integers(len(eligible)))]

    prompts = [[100 + i, i % 7] for i in range(len(specs))]
    reqs = [Request(prompt=p, arrival=float(a), priority=prio)
            for p, (a, prio) in zip(prompts, specs)]
    pool = ReplicaServingLoop([StubBackend() for _ in range(n_rep)],
                              STUB_SCFG, reqs, max_live=2,
                              cfg=ServingConfig(refill=True),
                              router=chaotic_router)
    got = pool.run()
    _assert_results_identical(_stub_serial(prompts), got)
    assert pool.slo.report()["n_finished"] == len(reqs)


def test_serving_loop_submit_matches_constructor():
    """submit() is equivalent to passing the request up front."""
    reqs = [Request(prompt=p, arrival=float(i))
            for i, p in enumerate(STUB_PROMPTS)]
    want = ServingLoop(StubBackend(), STUB_SCFG, reqs,
                       cfg=ServingConfig(refill=False)).run()
    loop = ServingLoop(StubBackend(), STUB_SCFG, [],
                       cfg=ServingConfig(refill=False))
    for i, r in enumerate(reqs):
        loop.submit(i, r)
    _assert_results_identical(want, loop.run())


# ---------------------------------------------------------------------------
# Multi-device placement (four virtual CPU devices, in a child process)
# ---------------------------------------------------------------------------

# A model=4 mesh needs four devices, and the CPU backend takes its
# device count from XLA_FLAGS before JAX starts: run it in a child.
_MODEL4_PROBE = """
import dataclasses, json, sys
import jax, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, PagedEngine

cfg = dataclasses.replace(get_config("tiny-lm"), n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128)
lm = build_model(cfg, remat=False)
params = lm.init(jax.random.key(0))

def probe(mesh):
    eng = PagedEngine(lm, params, EngineConfig(
        n_pages=64, page_size=8, max_batch=8, max_seq_len=64,
        attention=sys.argv[1], trace_logits=True, mesh=mesh))
    roots = eng.prefill_many([list(range(4, 4 + n)) for n in (17, 9)])
    kids = [k for r in roots for k in eng.branch(r, 2)]
    puts = eng.n_host_puts
    out = eng.decode(kids, 5, temperature=0.0,
                     row_keys=jax.random.split(jax.random.key(1), len(kids)))
    eng.alloc.check_invariants()
    devs = len(eng.pool.k.devices())
    puts = (eng.n_host_puts - puts) / eng.n_decode_steps
    return ([out[k] for k in kids], [np.asarray(a) for a in eng.logits_trace],
            devs, puts, eng)

want, want_logits, _, want_puts, _ = probe(None)
got, got_logits, devs, puts, eng = probe(make_host_mesh(model=4))
gap = max(float(abs(a - b).max()) for a, b in zip(got_logits, want_logits))
n = eng.n_host_puts
row = eng._put_rows(np.zeros(8, np.int32))
print(json.dumps({"devices": jax.device_count(), "pool_devices": devs,
                  "same_tokens": got == want, "gap": gap,
                  "puts_per_iter": [want_puts, puts],
                  "row_put": [isinstance(row, jax.Array),
                              len(row.sharding.device_set),
                              eng.n_host_puts - n]}))
"""


def _run_on_four_devices(probe, *args):
    """Run ``probe`` in a child with four CPU devices; returns the JSON
    object it printed last."""
    import json
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", probe, *args],
                       cwd=repo, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("attention", ["tree", "paged"])
def test_model4_mesh_engine_matches_meshless(attention):
    """A model=4 mesh engine (pool pages sharded over four devices, jnp
    attention) decodes the same greedy tokens as the mesh-less engine,
    with logits equal to f32 tolerance."""
    res = _run_on_four_devices(_MODEL4_PROBE, attention)
    assert res["devices"] == 4 and res["pool_devices"] == 4, res
    assert res["same_tokens"], res
    assert res["gap"] <= 1e-5, res
    # the mesh engine still commits each step operand itself, one
    # counted put apiece (tree: 6 per-row + 3 page-metadata operands;
    # paged: 6 per-row + the block tables); without a mesh none
    assert res["puts_per_iter"] == [0, {"tree": 9, "paged": 7}[attention]]
    assert res["row_put"] == [True, 4, 1], res


_REPLICA_PROBE = """
import dataclasses, json
import jax
from repro.configs import get_config
from repro.launch.mesh import replica_meshes
from repro.models.model import build_model
from repro.serving.engine import EngineConfig, PagedEngine

ids = lambda mesh: [d.id for d in mesh.devices.flat]
cfg = dataclasses.replace(get_config("tiny-lm"), n_layers=1, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=128)
lm = build_model(cfg, remat=False)
mesh = replica_meshes(4)[2]
eng = PagedEngine(lm, lm.init(jax.random.key(0)), EngineConfig(
    n_pages=16, page_size=8, max_batch=8, max_seq_len=64, mesh=mesh))
print(json.dumps({
    "four": [ids(m) for m in replica_meshes(4)],
    "two_by_model2": [[ids(m), m.shape["model"]]
                      for m in replica_meshes(2, model=2)],
    "three": [ids(m) for m in replica_meshes(3)],
    "six": [ids(m) for m in replica_meshes(6)],
    "pool": sorted(d.id for d in eng.pool.k.devices()),
    "params": sorted({d.id for leaf in jax.tree.leaves(eng.params)
                      for d in leaf.devices()}),
}))
"""


def test_replica_meshes_place_replicas_on_disjoint_devices():
    """Replicas split the host's devices into disjoint equal groups (a
    remainder idles; with fewer devices than replicas they share
    round-robin), and an engine commits its pool and weights to its
    own mesh."""
    res = _run_on_four_devices(_REPLICA_PROBE)
    assert res["four"] == [[0], [1], [2], [3]]
    assert res["two_by_model2"] == [[[0, 1], 2], [[2, 3], 2]]
    assert res["three"] == [[0], [1], [2]]
    assert res["six"] == [[0], [1], [2], [3], [0], [1]]
    assert res["pool"] == [2] and res["params"] == [2]
