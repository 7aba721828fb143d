"""The reduction of the program's own spans (``spans.py``): self time,
idle time by innermost ``repro.*`` span, the readings it gives, and
that the ``bench.*`` reduction does not see the program's spans."""
import glob
import gzip
import json
import os

import pytest

import bench_tiny as BT
from benchmarks.chip import harness as H
from benchmarks.chip import spans as SP
from benchmarks.chip import trace as TR
from benchmarks.chip import traffic as TF
from repro import obs

OPS = [["fusion.1", 100, 80], ["fusion.2", 300, 100], ["fusion.3", 950, 100]]
MODULES = [["jit_step(7)", 100, 80], ["jit_score_batch(9)", 300, 100]]
BENCH = [["bench.window", 0, 1000], ["bench.tick", 0, 1000],
         ["bench.decode_step", 90, 150]]
PROGRAM = [["repro.loop.tick", 5, 990], ["repro.engine.decode", 95, 140],
           ["repro.engine.decode.launch", 100, 20],
           ["repro.engine.decode.wait", 120, 80],
           ["repro.engine.decode.commit", 200, 30],
           ["repro.search.select", 500, 400], ["repro.ets.ilp", 600, 200],
           ["repro.runtime.gc", 650, 50]]


def _trace(host):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": TR.OPS_LINE, "events": OPS},
            {"name": TR.MODULES_LINE, "events": MODULES}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host}]}]}


def test_self_and_idle_time_on_a_hand_made_trace():
    r = SP.reduce(_trace(BENCH + PROGRAM))
    s = {n: {k: v * 1e9 for k, v in st.items() if k != "n"}
         for n, st in r["spans"].items()}
    # busy [100, 180) + [300, 400) + [950, 1000): 770 ns idle in 1000
    assert r["idle_s"] == pytest.approx(770e-9)
    want = {  # total, self, idle
        "loop.tick": (990, 450, 305), "engine.decode": (140, 10, 10),
        "engine.decode.launch": (20, 20, 0),
        "engine.decode.wait": (80, 80, 20),
        "engine.decode.commit": (30, 30, 30),
        "search.select": (400, 200, 200), "ets.ilp": (200, 150, 150),
        "runtime.gc": (50, 50, 50)}
    assert set(s) == set(want)
    for name, (total, own, idle) in want.items():
        assert (s[name]["total_s"], s[name]["self_s"], s[name]["idle_s"]) \
            == pytest.approx((total, own, idle)), name
    assert r["idle_outside_s"] == pytest.approx(5e-9)
    assert r["idle_outside_s"] + sum(st["idle_s"] for st in
                                     r["spans"].values()) \
        == pytest.approx(r["idle_s"])
    # crossed with the innermost bench.* span, the split adds up to
    # what trace.py puts on each bench span
    by = r["idle_by_bench"]
    assert by["decode_step"] == pytest.approx({
        "loop.tick": 10e-9, "engine.decode": 10e-9,
        "engine.decode.wait": 20e-9, "engine.decode.commit": 30e-9})
    bench_idle = TR.reduce(_trace(BENCH + PROGRAM))["idle_by_span_s"]
    assert {b: sum(v.values()) for b, v in by.items()} \
        == pytest.approx(bench_idle)
    assert SP.program_idle_gaps(r, 2) == [
        ["loop.tick", pytest.approx(305e-9)],
        ["search.select", pytest.approx(200e-9)]]


def test_readings_on_a_hand_made_trace():
    r = SP.reduce(_trace(BENCH + PROGRAM))
    assert SP.decode_host_ms_per_iter(r) == pytest.approx((140 - 80) * 1e-6)
    assert SP.select_ms_per_step(r) == pytest.approx(400e-6)
    assert SP.tick_max_ms(r) == pytest.approx(990e-6)
    assert SP.gc_ms(r) == pytest.approx(50e-6)
    no_gc = SP.reduce(_trace(BENCH + PROGRAM[:-1]))
    assert SP.gc_ms(no_gc) == 0.0
    # a program without spans of its own reads nothing, and raises not
    bare = SP.reduce(_trace(BENCH))
    assert bare["spans"] == {} and SP.program_idle_gaps(bare) == []
    for read in (SP.decode_host_ms_per_iter, SP.select_ms_per_step,
                 SP.tick_max_ms, SP.gc_ms):
        assert read(bare) is None and read(None) is None
    assert SP.prm_token_use({"n_scored_tokens": 49,
                             "n_scored_padded_tokens": 128}) \
        == pytest.approx(100 * 49 / 128)
    assert SP.prm_token_use({"n_decode_steps": 3}) is None
    assert SP.admit_wait_p50_s({1: 0.0, 2: 1.0, 3: 2.0},
                               {1: 0.5, 2: 1.25, 3: 4.0}) == 0.5
    assert SP.admit_wait_p50_s({1: 0.0}, {}) is None


def test_bench_reduction_does_not_see_the_programs_spans():
    assert TR.reduce(_trace(BENCH + PROGRAM)) == TR.reduce(_trace(BENCH))


def test_a_traced_tiny_window_reduces_to_readings(tmp_path):
    """On the CPU at a tiny size: a traced window of the closed cell
    holds the program's spans, and the readings read numbers."""
    import jax
    root = BT.make_root(str(tmp_path / "root"))
    cell, stack, _ = H.prepare(root, "tiny-closed", 2 ** 31 + 5,
                               use_kernel=False)
    c = cell.config
    traffic = TF.generate(cell.mix, 2 ** 31 + 5, 1.0, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))
    gcs = obs.gc_spans()
    trace_dir = str(tmp_path / "trace")

    def on_open():
        jax.profiler.start_trace(trace_dir)
        gcs.__enter__()

    def on_close():
        gcs.__exit__(None, None, None)
        jax.profiler.stop_trace()

    be = stack.backend
    n0 = {k: getattr(be, k) for k in SP.PRM_COUNTERS}
    H.run_window(cell, stack, traffic, 1.0, on_open=on_open,
                 on_close=on_close, min_finished=2)
    raw = TR.from_xplane(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                                   recursive=True)[0])
    r = SP.reduce(raw)
    assert {"loop.tick", "engine.decode", "engine.decode.wait",
            "search.select", "ets.ilp"} <= set(r["spans"])
    for read in (SP.decode_host_ms_per_iter, SP.select_ms_per_step,
                 SP.tick_max_ms, SP.gc_ms):
        assert read(r) is not None and read(r) >= 0
    use = SP.prm_token_use({k: getattr(be, k) - n0[k]
                            for k in SP.PRM_COUNTERS})
    assert 0 < use <= 100


EXCERPT = os.path.join(os.path.dirname(__file__), "data",
                       "trace_excerpt_v5e_spans.json.gz")


def test_recorded_v5e_excerpt_has_the_programs_spans_on_the_device_clock():
    """0.2 s of a traced phi3-w16-sweep window on one TPU v5e chip, from
    50 ms before a search step's selection (op texts cut to 100
    characters, host lines cut to the ``bench.*`` and ``repro.*``
    spans): the spans the readings read are there, and the host's wait
    on the sampled tokens overlaps the decode program ``step`` on the
    device, so both lie on one clock."""
    with gzip.open(EXCERPT, "rt") as f:
        t = json.load(f)
    ev = [(s, s + d) for p in t["planes"] if TR.is_device(p)
          for ln in p["lines"] if ln["name"] == TR.OPS_LINE
          for _, s, d in ln["events"]]
    r = SP.reduce(t, (min(a for a, _ in ev), max(b for _, b in ev)))
    assert {"loop.tick", "loop.seat", "engine.decode", "engine.decode.wait",
            "search.select", "ets.ilp", "backend.score"} <= set(r["spans"])
    assert set(r["spans"]) <= set(obs.SPANS)
    waits = [(s, s + d) for p in t["planes"] if not TR.is_device(p)
             for ln in p["lines"] for n, s, d in ln["events"]
             if n == "repro.engine.decode.wait"]
    steps = [(s, s + d) for p in t["planes"] if TR.is_device(p)
             for ln in p["lines"] if ln["name"] == TR.MODULES_LINE
             for n, s, d in ln["events"] if TR.program_name(n) == "step"]
    assert waits and steps
    assert any(a < d and c < b for a, b in waits for c, d in steps)
