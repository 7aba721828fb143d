"""Find the highest arrival rate an open cell sustains, once, by a
sweep on the chip.

    python3 benchmarks/chip/knee.py --workload <name> --seed <n> \\
        --seconds <s> --rates 1 2 3 ...

Each rate runs one window of the cell's traffic at that rate on the
same compiled stack and prints what was sent, answered, the time to
answer and the queue wait.  A rate is sustained while every request
sent is answered soon after the close and the queue wait does not grow
over the window.
"""
import argparse
import json
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    use_compile_cache()
    import jax
    if jax.default_backend() != "tpu":
        raise SystemExit("knee.py: no TPU; nothing was run")
    t0 = time.perf_counter()
    cell, stack, rec = H.prepare(ROOT, args.workload, args.seed)
    print(f"setup_s {time.perf_counter() - t0:.3f}", flush=True)
    c = cell.config
    for rate in args.rates:
        H.clear(stack)
        rec.reset()
        mix = dict(cell.mix, rate_per_s=rate)
        traffic = TF.generate(mix, args.seed, args.seconds, c["vocab_size"],
                              reserved=(c["step_token"], c["eos_token"]))
        win = H.run_window(cell, stack, traffic, args.seconds)
        half = len(win.queue_wait_s) // 2
        print(json.dumps({
            "rate_per_s": rate, "sent": win.attempted, "failed": win.failed,
            "window_s": win.window_s,
            "steps_per_s": win.steps_done / win.window_s,
            "tta_p50_s": H.percentile(win.tta_s, 50),
            "tta_p90_s": H.percentile(win.tta_s, 90),
            "queue_wait_p50_first_half_s":
                H.percentile(win.queue_wait_s[:half], 50),
            "queue_wait_p50_second_half_s":
                H.percentile(win.queue_wait_s[half:], 50)}), flush=True)


if __name__ == "__main__":
    main()
