"""Readings that the correctness limits are set from, many seeds in one
process.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1 2 3 ... --control 1 2 3 --seconds <s>

For each seed: new weights and traffic from the seed on the same
compiled stack, one window of the cell's own traffic (a closed cell
runs on until ``check.requests`` problems have finished), then the
readings of the numbers compared.  For the seeds in ``--control`` the
float8 reference is also put in the program's place and read.  One JSON
line per seed, then the largest program reading and the smallest
control reading of each number.  With ``--limits-out`` each number
whose smallest control reading is at least three times its largest
program reading gets the limit ``lower + 0.6 * (upper - lower)``, two
significant digits, rounded up: room on both sides, the more of it
above the program's readings.  A number that does not separate its two
readings gets no limit.
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from benchmarks.chip import harness as H  # noqa: E402
from benchmarks.chip import traffic as TF  # noqa: E402
from benchmarks.chip.run import use_compile_cache  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--limits-out", default=None)
    args = ap.parse_args(argv)
    use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit("calibrate.py: no TPU; nothing was run")
    t0 = time.perf_counter()
    cell, stack, rec = H.prepare(ROOT, args.workload, args.seeds[0])
    print(f"setup_s {time.perf_counter() - t0:.3f}", flush=True)
    c = cell.config
    lows, highs = {}, {}
    for i, seed in enumerate(args.seeds):
        if i:
            H.reseed(cell, stack, seed)
        H.clear(stack)
        rec.reset()
        traffic = TF.generate(cell.mix, seed, args.seconds, c["vocab_size"],
                              reserved=(c["step_token"], c["eos_token"]))
        win = H.run_window(cell, stack, traffic, args.seconds,
                           min_finished=cell.mix["check"]["requests"])
        t1 = time.perf_counter()
        r = H.check(cell, stack, rec, win, seed,
                    control=seed in args.control)
        line = {"seed": seed, "window_s": win.window_s,
                "finished": len(win.results), "check_s":
                time.perf_counter() - t1, "readings": r}
        print(json.dumps(line), flush=True)
        for k, v in r.items():
            if v["value"] is not None:
                lows[k] = max(lows.get(k, 0.0), v["value"])
            if v.get("control") is not None:
                highs[k] = min(highs.get(k, float("inf")), v["control"])
    print(json.dumps({"program_max": lows, "control_min": highs}),
          flush=True)
    if args.limits_out:
        limits = limits_from(lows, highs)
        os.makedirs(os.path.dirname(os.path.abspath(args.limits_out)),
                    exist_ok=True)
        with open(args.limits_out, "w") as f:
            json.dump(limits, f, indent=1)
        print(json.dumps({"limits": limits}), flush=True)


def limits_from(lows, highs):
    out = {}
    for k, lo in lows.items():
        hi = highs.get(k)
        if hi is None or hi < 3 * lo:
            continue
        x = lo + 0.6 * (hi - lo)
        step = 10 ** (math.floor(math.log10(x)) - 1)
        out[k] = float(f"{math.ceil(x / step) * step:.2g}")
    return out


if __name__ == "__main__":
    main()
