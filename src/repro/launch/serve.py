"""Serving launcher: an online SLO-tracked serving loop over an LM + PRM
+ embedder stack, or lower the serve step on the production mesh.

    # Poisson workload, token-level refill, SLO report (tiny LM, trained
    # on the arithmetic task first):
    PYTHONPATH=src python -m repro.launch.serve --rate 0.05 --requests 12

    # a published config at full width with seeded, untrained weights
    # (no training, no optimizer state; the vocab stays the published one):
    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \\
        --train-steps 0

    # replay a trace file (JSON list of {prompt, arrival, priority,
    # deadline}), lock-step baseline for comparison:
    PYTHONPATH=src python -m repro.launch.serve --trace trace.json \\
        --no-refill

    # four engine replicas behind one arrival stream, each on its own
    # devices (one chip each on a four-chip host):
    PYTHONPATH=src python -m repro.launch.serve --replicas 4

    # production-mesh lowering check (unchanged):
    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --dry-run

Without ``--trace`` the workload is Poisson arrivals over arithmetic-
task prompts at ``--rate`` requests per virtual time unit, with
optional ``--priorities`` classes and a ``--deadline-slack`` SLO.  The
clock is virtual (stage costs, not wall time), so runs are
deterministic in ``--seed``.

On a TPU backend the engines decode and prefill through the Pallas
kernels (``build_stack`` takes ``use_kernel`` from the platform); every
other platform runs the pure-jnp reference paths.
"""
import argparse
import json
import os
from typing import Optional, Sequence


def build_stack(arch: str = "tiny-lm", *, method: str = "ets",
                width: int = 8, train_steps: int = 250,
                meshes: Sequence = (None,), n_layers: Optional[int] = None):
    """Build the served stack: one ``LMBackend`` per entry of ``meshes``
    (``None``: the mesh-less single-device engine), and the search
    config they serve.

    The generator is ``arch`` (cut to ``n_layers`` if given), decoding
    with tree attention; the PRM is the same config at two layers with a
    value head; the embedder is ``tiny-embedder``.  All three share the
    generator's vocab.  With ``train_steps`` > 0 the vocab shrinks to
    the arithmetic task's and the LM and PRM are trained on it; with 0
    the weights are the seeded initialization at the config's published
    vocab, and no optimizer state is made.  Every replica holds
    identically seeded weights, so routing never changes an answer.
    ``use_kernel`` comes from the
    platform: the Pallas kernels on a TPU, wherever the engine's mesh
    admits them (one device), and the jnp references otherwise.
    """
    import dataclasses

    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.configs import get_config
    from repro.core import ETSConfig, SearchConfig
    from repro.models.model import build_model
    from repro.serving.engine import EngineConfig, PagedEngine
    from repro.serving.search_backend import BackendConfig, LMBackend
    from repro.training import TrainConfig, train_lm, train_prm
    from repro.training.task import (ArithmeticTask, EOS, NEWLINE,
                                     VOCAB_SIZE)

    lm_cfg = get_config(arch)
    if n_layers is not None:
        lm_cfg = dataclasses.replace(lm_cfg, n_layers=n_layers)
    if train_steps:
        lm_cfg = dataclasses.replace(lm_cfg, vocab_size=VOCAB_SIZE)
    task = ArithmeticTask(n_ops=4, seq_len=64)
    tcfg = TrainConfig(steps=train_steps, batch=32, log_every=10 ** 9)
    lm = build_model(lm_cfg, remat=False)
    # jitted init: the weights are made in place on the device, with no
    # eager per-layer copies
    lm_params, _ = train_lm(lm, jax.jit(lm.init)(jax.random.key(0)), task,
                            tcfg)
    prm = build_model(dataclasses.replace(lm_cfg, n_layers=2),
                      with_value_head=True, remat=False)
    prm_params, _ = train_prm(prm, jax.jit(prm.init)(jax.random.key(1)),
                              task, tcfg)
    emb = build_model(dataclasses.replace(get_config("tiny-embedder"),
                                          vocab_size=lm_cfg.vocab_size),
                      remat=False)
    emb_params = jax.jit(emb.init)(jax.random.key(2))
    on_tpu = jax.default_backend() == "tpu"

    def make_backend(mesh):
        ecfg = EngineConfig(
            n_pages=2048, page_size=8, max_batch=max(width * 2, 32),
            max_seq_len=200, attention="tree", mesh=mesh,
            use_kernel=on_tpu and (mesh is None or mesh.size == 1))
        engine = PagedEngine(lm, lm_params, ecfg)
        put = (lambda t: t) if mesh is None else (
            lambda t: jax.device_put(t, NamedSharding(mesh,
                                                      PartitionSpec())))
        return LMBackend(engine, prm, put(prm_params), emb, put(emb_params),
                         BackendConfig(step_token=NEWLINE, eos_token=EOS,
                                       max_step_tokens=12, max_depth=8),
                         answer_fn=ArithmeticTask.extract_answer,
                         seed=500)

    backends = [make_backend(m) for m in meshes]
    scfg = SearchConfig(method=method, width=width, max_steps=8,
                        ets=ETSConfig(lambda_b=2.0, lambda_d=1.0,
                                      cluster_threshold=0.15))
    return backends, scfg


def arithmetic_requests(n: int, rate: float, seed: int, **kw):
    """``n`` seeded arithmetic-task problems as Poisson requests; returns
    (requests, answers)."""
    import numpy as np

    from repro.core import poisson_requests
    from repro.training.task import ArithmeticTask, encode

    task = ArithmeticTask(n_ops=4, seq_len=64)
    rng = np.random.default_rng(seed)
    problems = [task.sample_problem(rng) for _ in range(n)]
    requests = poisson_requests([encode(p) for p, _, _ in problems],
                                rate=rate, seed=seed, **kw)
    return requests, [a for _, _, a in problems]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-lm")
    ap.add_argument("--method", default="ets",
                    choices=["beam", "dvts", "rebase", "ets", "ets-kv"])
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8,
                    help="Poisson workload size (ignored with --trace)")
    ap.add_argument("--rate", type=float, default=0.05,
                    help="arrival rate, requests per virtual time unit")
    ap.add_argument("--trace", default=None,
                    help="JSON request trace to replay instead of Poisson")
    ap.add_argument("--priorities", type=int, nargs="*", default=None,
                    help="priority classes cycled over Poisson arrivals")
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="per-request SLO: deadline = arrival + slack")
    ap.add_argument("--max-live", type=int, default=4,
                    help="per-replica live-problem bound")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the one arrival stream "
                         "(each gets its own devices, KV pool and spill "
                         "buffer)")
    ap.add_argument("--mesh", type=int, default=0, metavar="MODEL",
                    help="shard each engine's KV pool on a mesh of its "
                         "devices with this model-axis size (0: no mesh "
                         "for a single replica, one-device meshes for "
                         "several)")
    ap.add_argument("--no-refill", action="store_true",
                    help="lock-step barrier baseline (refill off)")
    ap.add_argument("--first-finish", action="store_true",
                    help="halt each problem at its first completed answer")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train-steps", type=int, default=250)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512 "
            + os.environ.get("XLA_FLAGS", ""))
        from repro.launch.dryrun import lower_combo
        rec = lower_combo(args.arch, args.shape, multi_pod=args.multi_pod)
        print(rec.get("status"), rec.get("memory", rec.get("error")))
        return

    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    from repro.core import ServingConfig, ServingLoop, load_trace

    meshes = [None]
    if args.replicas > 1 or args.mesh:
        from repro.launch.mesh import replica_meshes
        meshes = replica_meshes(max(args.replicas, 1),
                                model=max(args.mesh, 1))
    backends, scfg = build_stack(args.arch, method=args.method,
                                 width=args.width,
                                 train_steps=args.train_steps,
                                 meshes=meshes)

    if args.trace:
        requests = load_trace(args.trace)
        answers = None
    else:
        requests, answers = arithmetic_requests(
            args.requests, args.rate, args.seed,
            priorities=args.priorities, deadline_slack=args.deadline_slack)

    svc = ServingConfig(refill=not args.no_refill,
                        first_finish=args.first_finish)
    if len(backends) > 1:
        from repro.core import ReplicaServingLoop
        loop = ReplicaServingLoop(backends, scfg, requests,
                                  max_live=args.max_live, cfg=svc)
    else:
        loop = ServingLoop(backends[0], scfg, requests,
                           max_live=args.max_live, cfg=svc)
    results = loop.run()

    rep = loop.slo.report()
    mode = "lock-step" if args.no_refill else "refill"
    print(f"\n== online serving ({len(requests)} requests, {mode}"
          f"{', first-finish' if args.first_finish else ''}, "
          f"replicas={len(backends)}, max_live={args.max_live}) ==")
    for k in ("n_finished", "p50_tta", "p90_tta", "p99_tta", "mean_tta",
              "max_tta", "deadline_hit_rate"):
        v = rep.get(k)
        print(f"  {k:18s}: "
              + (f"{v:.2f}" if isinstance(v, float) else str(v)))
    if answers is not None:
        acc = sum(int(r.answer == a)
                  for r, a in zip(results, answers)) / len(answers)
        print(f"  {'accuracy':18s}: {acc:.2f}")
    print(json.dumps(rep))


if __name__ == "__main__":
    main()
