"""The traffic generator: deterministic in the seed, the same work for
every seed, and the open-loop rate the mix file states."""
import json
import os

import numpy as np

import bench_tiny as BT
from benchmarks.chip import traffic as TF

MIXES = os.path.join(BT.REPO, BT.DATA, "traffic")


def _mixes():
    return {f[:-5]: json.load(open(os.path.join(MIXES, f)))
            for f in sorted(os.listdir(MIXES)) if f.endswith(".json")}


def test_same_seed_same_traffic_other_seed_other_order():
    for mix in _mixes().values():
        a = TF.generate(mix, 2 ** 31 + 11, 40.0, 32064, reserved=(13,))
        b = TF.generate(mix, 2 ** 31 + 11, 40.0, 32064, reserved=(13,))
        c = TF.generate(mix, 2 ** 31 + 12, 40.0, 32064, reserved=(13,))
        assert a.prompts == b.prompts and a.due_s == b.due_s
        assert a.prompts != c.prompts
        # every seed carries the same multiset of sizes and gaps
        assert sorted(map(len, a.prompts)) == sorted(map(len, c.prompts))
        assert np.allclose(sorted(np.diff([0.0] + a.due_s)),
                           sorted(np.diff([0.0] + c.due_s)))
        assert all(13 not in p for p in a.prompts)


def test_closed_backlog_blocks_hold_the_same_work_for_every_seed():
    for mix in _mixes().values():
        if mix["mode"] != "closed":
            continue
        k = mix["max_live"]
        runs = [[len(p) for p in TF.generate(mix, s, 40.0, 32064).prompts]
                for s in (2 ** 31 + 21, 2 ** 31 + 22, 7)]
        assert runs[0] != runs[1]
        for lens in runs:
            assert len(lens) == mix["backlog"]
            for b in range(0, len(lens), k):
                assert sorted(lens[b:b + k]) == sorted(runs[0][:k])


def test_prompt_lengths_span_the_mix_range():
    for mix in _mixes().values():
        lo, hi = mix["prompt_len"]
        t = TF.generate(mix, 5, 40.0, 64000)
        lens = [len(p) for p in t.prompts]
        assert lo <= min(lens) and max(lens) <= hi
        assert abs(np.mean(lens) - (lo + hi) / 2) < 0.02 * (hi - lo) + 1


def test_open_loop_rate_matches_the_mix_file():
    for name, mix in _mixes().items():
        if mix["mode"] != "open":
            continue
        seconds = 40.0
        t = TF.generate(mix, 9, seconds, 64000)
        assert len(t.prompts) == int(mix["rate_per_s"] * seconds)
        assert t.due_s == sorted(t.due_s) and t.due_s[-1] <= seconds
        rate = len(t.due_s) / t.due_s[-1]
        assert abs(rate / mix["rate_per_s"] - 1) < 0.05, (name, rate)
        gaps = np.diff([0.0] + t.due_s)
        # exponential gaps: the spread equals the mean
        assert abs(gaps.std() / gaps.mean() - 1) < 0.15


def test_shared_prefix_is_the_same_for_every_prompt_of_a_run():
    mix = dict(_mixes()["w16-sweep"], shared_prefix_len=40)
    a = TF.generate(mix, 2 ** 31 + 5, 40.0, 32064)
    b = TF.generate(mix, 2 ** 31 + 6, 40.0, 32064)
    lo, hi = mix["prompt_len"]
    assert all(p[:40] == a.prompts[0][:40] for p in a.prompts)
    assert a.prompts[0][:40] != b.prompts[0][:40]
    assert all(lo + 40 <= len(p) <= hi + 40 for p in a.prompts)


def test_bursts_keep_the_mean_rate_and_send_nothing_between_them():
    mix = dict(_mixes()["w4-open"], burst={"on_s": 2.0, "off_s": 3.0})
    seconds = 40.0
    t = TF.generate(mix, 2 ** 31 + 7, seconds, 64000)
    due = np.asarray(t.due_s)
    assert len(due) == int(mix["rate_per_s"] * seconds)
    assert (np.diff(due) >= 0).all() and due[-1] <= seconds
    assert (np.mod(due, 5.0) <= 2.0).all()
    assert abs(len(due) / due[-1] / mix["rate_per_s"] - 1) < 0.1


def test_sub_seeds_take_large_seeds():
    big = 2 ** 31 + 2 ** 20
    assert TF.sub_seed(big, 1) != TF.sub_seed(big + 1, 1)
    assert TF.sub_seed(big, 1) != TF.sub_seed(big, 2)
    assert 0 <= TF.sub_seed(2 ** 40, 3) < 2 ** 32
