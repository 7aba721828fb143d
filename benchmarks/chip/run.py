"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout.  The run makes its weights and traffic from ``--seed``, warms
every shape the cell's traffic uses (set-up), drives the serving loop
for ``--seconds`` of wall time, then compares a sample of what the
window served with the plain reference.  ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` profiles the window and
reports its per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when tracing), with ``checks`` last: each number compared beside its
limit.  The same numbers end standard error.  Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import trace as TR  # noqa: E402
from benchmarks.chip import traffic as TF  # noqa: E402

CACHE = os.path.join(ROOT, ".jax_cache")


def use_compile_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or the
    checkout's ``.jax_cache``, holding every program however small, so
    that only a cell's first run in a checkout compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def say(*a):
    print(*a, flush=True)


def measure(cell, seed, seconds, trace, *, root=ROOT, require_tpu=True,
            peak=None, dump_trace=None):
    """One run of ``cell``; returns the result object."""
    import jax
    if require_tpu and (jax.default_backend() != "tpu"
                        or jax.device_count() < cell.chips):
        raise SystemExit(
            f"run.py: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"has {jax.device_count()} {jax.default_backend()} device(s). "
            f"Nothing was run.")
    dev = jax.devices()[0]
    if peak is None:
        peak = H.peaks(root, dev.device_kind)
    stack = H.build(cell, seed, use_kernel=require_tpu)
    say(f"built: {time.perf_counter() - T_START:.3f} s after start")
    warmed = H.warm(cell, stack)
    rec = H.Recorder(stack)
    c = cell.config
    traffic = TF.generate(cell.mix, seed, seconds, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))
    compiles = H.CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    setup = {}

    def on_open():
        compiles.armed = True
        if trace_dir:       # device and runtime events, no Python tracer
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup["s"] = time.perf_counter() - T_START

    def on_close():
        compiles.armed = False
        if trace_dir:
            jax.profiler.stop_trace()

    win = H.run_window(cell, stack, traffic, seconds, on_open=on_open,
                       on_close=on_close)
    stats = dev.memory_stats() or {}
    say(f"warmed shapes: {warmed}")
    say(f"window_s: {win.window_s!r}  attempted: {win.attempted}  "
        f"failed: {win.failed}  finished: {len(win.results)}  "
        f"partway through a step at the close: {win.mid_step}  "
        f"sent late by p50 {H.percentile(win.late_s, 50)!r} s, max "
        f"{max(win.late_s, default=None)!r} s")
    say(f"compiles inside the window: {compiles.n}  trace counters: " +
        ", ".join(f"{k} +{win.counters[k]}" for k in (
            "decode_traces", "prefill_traces", "score_traces",
            "embed_traces")))
    say(f"counters: {win.counters}")
    reduced = None
    if trace_dir:
        pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
        raw = TR.from_xplane(pb[0])
        if dump_trace:       # the window's first 0.2 s, and what it holds
            lo, _ = TR.window_of(raw)
            with open(dump_trace, "w") as f:
                json.dump({"lines": {p["name"]: {ln["name"]: len(ln["events"])
                                                  for ln in p["lines"]}
                                     for p in raw["planes"]},
                           "excerpt": TR.excerpt(raw, lo, lo + 200_000_000)},
                          f)
        reduced = TR.reduce(raw)
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace: busy_s {reduced['busy_s']!r} window_s "
            f"{reduced['window_s']!r} programs_s {reduced['programs_s']}")
    ctx = H.layer_context(cell, rec, win, reduced, peak)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = H.metric_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        vals = H.end_to_end(cell, win, setup["s"])
        for m in cell.end_to_end:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if reduced:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    # the program's state goes before the reference runs
    stack.engine.pool.k = stack.engine.pool.v = None
    readings = H.check(cell, stack, rec, win, seed)
    checks = {k: {"value": r["value"], "limit": cell.limits.get(k)}
              for k, r in readings.items()}
    out = {"correct": H.judge(readings, cell.limits) and win.attempted > 0,
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if reduced:
        out["breakdown"] = {"device_ops": TR.top(reduced["ops_s"]),
                            "idle_gaps": TR.top(reduced["idle_by_span_s"])}
    out["checks"] = checks
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="also write the traced window's first 0.2 s as "
                         "plain JSON here")
    args = ap.parse_args(argv)
    cell = H.load_cell(ROOT, args.workload)
    use_compile_cache()
    out = measure(cell, args.seed, args.seconds, bool(args.trace),
                  dump_trace=args.dump_trace)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    say(json.dumps(out))


if __name__ == "__main__":
    main()
