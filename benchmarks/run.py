"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,table1,...] [--fast]

Each module's run() prints a human-readable table and returns a dict that
is archived under experiments/bench/.  The table2 rows are additionally
written to ``BENCH_table2.json`` (repo root by default) — the
machine-readable perf record (tokens/s, decode calls/step, pages
streamed per decode step for serial / batched-paged / batched-tree,
the prefill-ingestion section: serial-dense vs batched-flash prompt
tok/s, the kernels section: leaf-tiled vs full-batch-tile tree
attention decode tok/s + per-tile scratch bytes,
the sweep section: one-at-a-time vs continuous cross-problem
problems/s + mean batch occupancy, the pressure section:
serialized vs demotion-enabled small-pool problems/s, and the serving
section: lock-step vs token-level-refill p50/p99 time-to-answer per
Poisson arrival rate on the serving loop's virtual clock) that tracks
the serving trajectory across PRs; CI uploads
it as an artifact from the smoke invocation and
``benchmarks/trend_check.py`` fails the smoke job on a >2x tok/s
regression against the committed copy (serving rows gate on p99
time-to-answer, where LOWER is better; adaptive rows gate on accuracy,
which is deterministic and must not regress at all — and any BENCH
``acc`` field that is exactly 0.0 fails outright).  The serving rows
are also written to ``<out>/serving_latency_curve.json`` and the
adaptive accuracy-vs-tokens frontier to
``<out>/adaptive_frontier.json`` — artifacts the slow CI job uploads.

``--smoke`` shrinks everything to a tiny 2-step configuration that
finishes in a couple of minutes on CPU — a liveness check for the whole
measured stack, not a meaningful measurement.
"""
import argparse
import json
import os
import time


def main() -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,table1,table2,table3")
    ap.add_argument("--fast", action="store_true",
                    help="smaller problem counts / widths")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 2-step CI liveness run (implies --fast)")
    ap.add_argument("--out", default="experiments/bench")
    ap.add_argument("--bench-json", default="BENCH_table2.json",
                    help="where to write the machine-readable table2 rows")
    args = ap.parse_args()
    args.fast = args.fast or args.smoke
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (fig2_proxy_metrics, table1_kv_reduction,
                            table2_throughput, table3_ablation)

    # one jobs table; smoke/fast only shrink the per-job parameters
    if args.smoke:
        # t2 smoke is sized so every decode row's accuracy is non-zero
        # (an easier 2-op task, enough training, and enough search
        # steps to complete trajectories) — the trend check fails any
        # BENCH section whose acc is exactly 0.0, because a zero means
        # the row measured a stack that never produced an answer
        p = dict(fig2_problems=4, fig2_io=dict(io_width=6, io_problems=1),
                 t1_widths=(16,), t1_problems=6,
                 t2=dict(train_steps=240, n_problems=2, width=6,
                         max_steps=4, task_ops=2),
                 t3_problems=8)
    elif args.fast:
        p = dict(fig2_problems=16, fig2_io={},
                 t1_widths=(16, 64), t1_problems=30,
                 t2=dict(train_steps=60, n_problems=3),
                 t3_problems=30)
    else:
        p = dict(fig2_problems=40, fig2_io={},
                 t1_widths=(16, 64, 256), t1_problems=60,
                 t2=dict(train_steps=150, n_problems=6),
                 t3_problems=100)
    jobs = {
        "fig2": lambda: fig2_proxy_metrics.run(
            n_problems=p["fig2_problems"], **p["fig2_io"]),
        "table1": lambda: table1_kv_reduction.run(
            widths=p["t1_widths"], n_problems=p["t1_problems"]),
        "table2": lambda: table2_throughput.run(**p["t2"]),
        "table3": lambda: table3_ablation.run(n_problems=p["t3_problems"]),
    }
    os.makedirs(args.out, exist_ok=True)
    for name, job in jobs.items():
        if only and name not in only:
            continue
        t0 = time.time()
        res = job()
        res["wall_s"] = round(time.time() - t0, 1)
        with open(os.path.join(args.out, name + ".json"), "w") as f:
            json.dump(res, f, indent=1, default=str)
        if name == "table2":
            with open(args.bench_json, "w") as f:
                json.dump({"smoke": args.smoke, "fast": args.fast,
                           "rows": res["rows"],
                           "prefill": res.get("prefill", []),
                           "kernels": res.get("kernels", []),
                           "sweep": res.get("sweep", []),
                           "pressure": res.get("pressure", []),
                           "serving": res.get("serving", []),
                           "adaptive": res.get("adaptive", []),
                           "mesh": res.get("mesh", []),
                           "families": res.get("families", [])},
                          f, indent=1, default=str)
            print(f"[table2] rows -> {args.bench_json}")
            stage = os.path.join(args.out, "stage_costs.json")
            with open(stage, "w") as f:
                json.dump({"smoke": args.smoke, "fast": args.fast,
                           **res.get("stage_costs", {})},
                          f, indent=1, default=str)
            print(f"[table2] stage-cost calibration -> {stage}")
            curve = os.path.join(args.out, "serving_latency_curve.json")
            with open(curve, "w") as f:
                json.dump({"smoke": args.smoke, "fast": args.fast,
                           "rows": res.get("serving", [])},
                          f, indent=1, default=str)
            print(f"[table2] serving latency curve -> {curve}")
            frontier = os.path.join(args.out, "adaptive_frontier.json")
            with open(frontier, "w") as f:
                json.dump({"smoke": args.smoke, "fast": args.fast,
                           "rows": res.get("adaptive", [])},
                          f, indent=1, default=str)
            print(f"[table2] adaptive frontier -> {frontier}")
        print(f"[{name}] done in {res['wall_s']}s\n")


if __name__ == "__main__":
    main()
