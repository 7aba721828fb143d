"""The trace reduction: busy union, idle share, program and kernel time
and idle gaps by host span, on a hand-made trace."""
import gzip
import json
import os

import pytest

import bench_tiny  # noqa: F401  (puts the repository on the path)
from benchmarks.chip import trace as TR


def _trace(ops, modules, host):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": TR.OPS_LINE, "events": ops},
            {"name": TR.MODULES_LINE, "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]}]}


def test_hand_made_trace():
    ops = [["fusion.1", 100, 50], ["tree_attention", 120, 60],
           ["fusion.2", 300, 100], ["fusion.3", 950, 100]]
    modules = [["jit_step(7)", 100, 80], ["jit_score_batch(9)", 300, 100]]
    host = [["bench.window", 0, 1000], ["bench.tick", 0, 1000],
            ["bench.decode_step", 90, 150], ["bench.score", 250, 200]]
    r = TR.reduce(_trace(ops, modules, host))
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 180) + [300, 400) + [950, 1000) clipped to the window
    assert r["busy_s"] == pytest.approx(230e-9)
    assert r["idle_share"] == pytest.approx(0.77)
    assert r["programs_s"] == pytest.approx({"step": 80e-9,
                                             "score_batch": 100e-9})
    assert r["ops_s"]["tree_attention"] == pytest.approx(60e-9)
    idle = r["idle_by_span_s"]
    # idle [0,100): tick 90 + decode_step 10; [180,300): decode_step 60,
    # tick 10, score 50; [400,950): score 50, tick 500
    assert idle == pytest.approx({"tick": 600e-9, "decode_step": 70e-9,
                                  "score": 100e-9})
    assert sum(idle.values()) == pytest.approx(1000e-9 - r["busy_s"])


EXCERPT = os.path.join(os.path.dirname(__file__), "data",
                       "trace_excerpt_v5e.json.gz")


def test_recorded_v5e_excerpt_has_the_names_the_readers_read():
    """0.2 s of a traced phi3-w16-sweep window on one TPU v5e chip (op
    texts cut to 100 characters, host lines cut to the benchmark's
    spans): the programs and the kernel the readers look for are
    there under the names they use."""
    with gzip.open(EXCERPT, "rt") as f:
        t = json.load(f)
    ev = [(s, s + d) for p in t["planes"] if TR.is_device(p)
          for ln in p["lines"] if ln["name"] == TR.OPS_LINE
          for _, s, d in ln["events"]]
    r = TR.reduce(t, (min(a for a, _ in ev), max(b for _, b in ev)))
    assert {"step", "prefill"} <= set(r["programs_s"])
    assert 0 < r["busy_s"] <= r["window_s"]
    # the tree kernel runs once per layer of the 4-layer generator
    tree = {TR.op_name(k) for k in r["ops_s"]
            if TR.op_name(k).startswith("tree_attention")}
    assert tree == {"tree_attention", "tree_attention.1",
                    "tree_attention.2", "tree_attention.3"}
    assert r["program_calls"]["step"] == 4
    assert TR.kernel_s(r, "flash_prefill") > 0
    assert set(r["idle_by_span_s"]) <= {"tick", "prefill", "expand_begin",
                                        "decode_step", "expand_finish",
                                        "score", "embed", "host"}


def test_an_operation_that_takes_the_kernels_output_is_not_the_kernel():
    reduced = {"n_devices": 1, "ops_s": {
        "%tree_attention.1 = f32[64,32,96] custom-call(f32[64,32,96] %q)": 2.0,
        "%fusion.7 = f32[64,3072] fusion(f32[64,32,96] %tree_attention.1)":
            5.0,
        "%tree_attention_prep = f32[64] fusion(f32[64] %x)": 7.0}}
    assert TR.kernel_s(reduced, "tree_attention") == 2.0
    with pytest.raises(KeyError):
        TR.kernel_s(reduced, "flash_prefill")
