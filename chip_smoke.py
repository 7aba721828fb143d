"""On-chip smoke test of the tree-search serving path.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # four TPU chips of one host

One chip: builds the served stack at llama3.2-1b's published widths
(seeded weights, ``launch.serve.build_stack``), serves eight seeded
arithmetic requests through the online ``ServingLoop`` with ETS at
width 8 over the tree-attention Pallas kernel, and checks every request
is answered, the allocator's invariants hold, and the compiled decode
step holds the kernel.  Then one prefill and a few greedy decode steps
of the tree-kernel, paged-kernel and jnp-reference engines on the same
branched batch must give identical tokens and logits within ``TOL``.

Four chips (``--chips 4``) runs only the multi-chip paths, at the same
widths cut to ``FOUR_CHIP_LAYERS`` layers: four engine replicas, each on
its own chip, against one replica on chip 0 (both at ``max_live=1``, so
every problem sees the same batches and the results must be equal), and
a ``model=4`` mesh engine against the one-chip engine on the same jnp
path.  Depth is cut because each replica compiles its own programs for
its own chip, and what these paths check (placement, and equality
across chips) does not depend on depth.

Everything runs in this one process: a chip belongs to one process at a
time.  With no TPU the script exits non-zero and prints no result.  Its
last line of standard output is ``{"ok": true, "device": {...}}``.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.cache import use_compile_cache  # noqa: E402

ARCH = "llama3.2-1b"
WIDTH = 8
N_REQUESTS = 8
DECODE_STEPS = 4
FOUR_CHIP_LAYERS = 2
# f32 agreement bound for logits: max |a - b| <= TOL * (1 + max |ref|),
# with every matmul at f32 ("highest") precision on both sides
TOL = 1e-3


def say(key, value):
    print(f"{key}: {value}", flush=True)


def check(ok, what):
    """Fail the run (also under ``python -O``, which drops asserts)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _signature(result):
    """A search result's identity: answer, completed trajectories, step
    count, and every tree node's structure, reward and tokens."""
    nodes = [(n.id, n.parent, n.n_tokens, n.reward, n.finished,
              list(n.payload.get("tokens") or ())
              if isinstance(n.payload, dict) else None)
             for n in result.tree.nodes]
    return result.answer, result.completed, result.steps, nodes


def _decode_step_text(engine):
    """Compiled text of the engine's tree decode step at the smallest
    page bucket (the run compiled the same program)."""
    import jax.numpy as jnp
    B = engine.ecfg.max_batch
    ints = lambda *shape: jnp.zeros(shape, jnp.int32)
    args = (engine.params, ints(B), ints(B), ints(B), ints(B),
            jnp.zeros(B, bool), ints(8), jnp.zeros((8, B), jnp.int8),
            ints(8), ints(B), engine.pool.k, engine.pool.v,
            engine._state_in())
    return engine._tree_decode_fn.lower(*args).compile().as_text()


def _greedy_probe(model, params, ecfg, prompts):
    """One prefill of ``prompts``, two branches per prompt, then
    ``DECODE_STEPS`` greedy lock-step decode steps of the branches.
    Returns (tokens per branch, [prefill logits, step logits...]) with
    inactive rows dropped."""
    import jax
    import numpy as np
    from repro.serving.engine import PagedEngine
    eng = PagedEngine(model, params,
                      dataclasses.replace(ecfg, trace_logits=True))
    with jax.default_matmul_precision("highest"):
        roots = eng.prefill_many(prompts)
        kids = [k for r in roots for k in eng.branch(r, 2)]
        out = eng.decode(kids, DECODE_STEPS, temperature=0.0,
                         row_keys=jax.random.split(jax.random.key(0),
                                                   len(kids)))
    logits = [np.asarray(eng.logits_trace[0])[:len(prompts)]]
    logits += [np.asarray(a)[:len(kids)] for a in eng.logits_trace[1:]]
    eng.alloc.check_invariants()
    return [out[k] for k in kids], logits


def _agree(name, got, want):
    """Tokens identical and logits within TOL; prints the measured gap."""
    toks, logits = got
    ref_toks, ref_logits = want
    check(toks == ref_toks, (name, toks, ref_toks))
    check(len(logits) == len(ref_logits), name)
    gap = max(float(abs(a - b).max()) for a, b in zip(logits, ref_logits))
    bound = TOL * (1.0 + max(float(abs(b).max()) for b in ref_logits))
    say(f"{name} max |logit diff|", f"{gap:.3e} (bound {bound:.3e})")
    check(gap <= bound, (name, gap, bound))


def _probe_prompts(n):
    from repro.launch.serve import arithmetic_requests
    requests, _ = arithmetic_requests(n, rate=1.0, seed=1)
    return [r.prompt for r in requests]


def one_chip(arch=ARCH):
    """The served path on one chip.  Returns the engine's compiled
    decode-step text for the caller's kernel check."""
    from repro.core import ServingLoop
    from repro.launch.serve import arithmetic_requests, build_stack

    t0 = time.perf_counter()
    backends, scfg = build_stack(arch, width=WIDTH, train_steps=0)
    say("stack_build_s", f"{time.perf_counter() - t0:.3f}")
    backend = backends[0]
    engine = backend.engine
    cfg = engine.cfg
    say("model", f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}")
    say("engine", f"attention={engine.ecfg.attention} "
        f"use_kernel={engine.ecfg.use_kernel} n_pages={engine.ecfg.n_pages} "
        f"max_batch={engine.ecfg.max_batch}")
    requests, _ = arithmetic_requests(N_REQUESTS, rate=0.05, seed=0)
    # first pass compiles every shape the run meets: it is set-up
    warm = ServingLoop(backend, scfg, requests, max_live=4).run()
    say("setup_s (stack build + compiling pass)",
        f"{time.perf_counter() - t0:.3f}")

    tokens0 = engine.n_decoded_tokens
    t1 = time.perf_counter()
    loop = ServingLoop(backend, scfg, requests, max_live=4)
    results = loop.run()
    wall = time.perf_counter() - t1
    generated = engine.n_decoded_tokens - tokens0
    say("run_wall_s", f"{wall:.3f}")
    say("generated_tokens", generated)
    report = loop.slo.report()
    check(report["n_finished"] == N_REQUESTS, report)
    check(len(results) == N_REQUESTS, "results")
    check(all(r.steps > 0 and len(r.tree) > 1 for r in results),
          "a request got no search step")
    say("requests_answered", f"{report['n_finished']}/{N_REQUESTS}")
    check([_signature(r) for r in results] ==
          [_signature(r) for r in warm], "second pass diverged")
    say("second pass identical to first", True)
    engine.alloc.check_invariants()
    check(engine.alloc.used_pages == 0, "pages leaked")
    say("alloc.check_invariants", "ok")

    text = _decode_step_text(engine)
    say("decode step has tpu_custom_call", "tpu_custom_call" in text)

    prompts = _probe_prompts(4)
    base = dataclasses.replace(engine.ecfg, n_pages=256)
    runs = {name: _greedy_probe(engine.model, engine.params,
                                dataclasses.replace(base, attention=att,
                                                    use_kernel=kern),
                                prompts)
            for name, att, kern in (("tree-kernel", "tree", True),
                                    ("paged-kernel", "paged", True),
                                    ("jnp-reference", "paged", False))}
    for name in ("tree-kernel", "paged-kernel"):
        _agree(f"{name} vs jnp-reference", runs[name], runs["jnp-reference"])
    say("tree, paged and reference engines agree", True)
    stats = engine.pool.k.devices().pop().memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        say("peak_device_memory_GiB", f"{stats['peak_bytes_in_use'] / 2**30:.3f}")
    return text


def four_chips(arch=ARCH, n_layers=FOUR_CHIP_LAYERS):
    """The multi-chip paths: replicas on distinct chips, a model=4 mesh."""
    import jax
    from repro.core import ReplicaServingLoop, ServingLoop
    from repro.launch.mesh import make_host_mesh, replica_meshes
    from repro.launch.serve import arithmetic_requests, build_stack

    check(jax.device_count() == 4, jax.devices())
    t0 = time.perf_counter()
    backends, scfg = build_stack(arch, width=WIDTH, train_steps=0,
                                 meshes=replica_meshes(4), n_layers=n_layers)
    cfg = backends[0].engine.cfg
    say("model", f"{cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size}")
    homes = []
    for b in backends:
        pool = b.engine.pool.k.devices()
        weights = {d for leaf in jax.tree.leaves(b.engine.params)
                   for d in leaf.devices()}
        check(len(pool) == 1 and weights == pool, (pool, weights))
        homes.append(next(iter(pool)))
    check(len(set(homes)) == 4, homes)
    say("replica devices", [d.id for d in homes])

    requests, _ = arithmetic_requests(N_REQUESTS, rate=0.05, seed=0)
    fleet = ReplicaServingLoop(backends, scfg, requests, max_live=1)
    got = fleet.run()
    say("replica routing", [fleet.routed[i] for i in range(N_REQUESTS)])
    check(len(set(fleet.routed.values())) == 4, fleet.routed)
    t1 = time.perf_counter()
    want = ServingLoop(backends[0], scfg, requests, max_live=1).run()
    say("one-replica run_wall_s (warm, chip 0)",
        f"{time.perf_counter() - t1:.3f}")
    check([_signature(r) for r in got] == [_signature(r) for r in want],
          "4 replicas diverged from 1 replica")
    say("4 replicas == 1 replica per problem", True)
    say("replicas phase_s", f"{time.perf_counter() - t0:.3f}")

    model, params = backends[0].engine.model, backends[0].engine.params
    base = dataclasses.replace(backends[0].engine.ecfg, n_pages=256,
                               mesh=None, use_kernel=False)
    del backends, fleet
    t2 = time.perf_counter()
    prompts = _probe_prompts(4)
    one = _greedy_probe(model, params, base, prompts)
    mesh = make_host_mesh(model=4)
    sharded = _greedy_probe(model, params,
                            dataclasses.replace(base, mesh=mesh), prompts)
    _agree("model=4 mesh vs one chip", sharded, one)
    say("mesh phase_s", f"{time.perf_counter() - t2:.3f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend is "
                 f"{jax.default_backend()!r}); nothing was run")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say("device", device)
    if args.chips == 4:
        four_chips()
    else:
        text = one_chip()
        check("tpu_custom_call" in text, "decode step runs no kernel")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
