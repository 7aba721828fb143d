"""The harness end to end on the CPU at a tiny size: a sound run is
correct, a run whose timed path is broken underneath is not, and the
float8 control fails the cells' own limits."""
import glob
import json
import os

import pytest

import bench_tiny as BT
from benchmarks.chip import harness as H
from benchmarks.chip import traffic as TF


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return BT.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.fixture(scope="module")
def closed(root):
    return H.prepare(root, "tiny-closed", 2 ** 31 + 3, use_kernel=False)


def _window(closed, seed):
    cell, stack, rec = closed
    H.clear(stack)
    rec.reset()
    c = cell.config
    traffic = TF.generate(cell.mix, seed, 1.0, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))
    return H.run_window(cell, stack, traffic, 1.0, min_finished=2)


def _readings(closed, seed, **kw):
    cell, stack, rec = closed
    win = _window(closed, seed)
    return cell, win, H.check(cell, stack, rec, win, seed, **kw)


def test_sound_run_is_correct(closed):
    cell, win, r = _readings(closed, 11)
    assert win.attempted > 0 and len(win.results) >= 2
    assert r["token_gap"]["n"] > 0 and r["prm_gap"]["n"] > 0
    assert H.judge(r, cell.limits), r


def test_closed_window_ends_between_steps(closed):
    """A window whose time is up while its problems are partway
    through a step runs on to the step's end, and counts it."""
    cell, stack, rec = closed
    H.clear(stack)
    rec.reset()
    c = cell.config
    traffic = TF.generate(cell.mix, 2 ** 31 + 17, 1.0, c["vocab_size"],
                          reserved=(c["step_token"], c["eos_token"]))
    win = H.run_window(cell, stack, traffic, 1e-3)
    assert win.mid_step == 0
    assert win.steps_done == cell.mix["max_live"]
    assert not win.results


def test_token_altered_where_produced_is_not_correct(closed, monkeypatch):
    from repro.serving import sampler
    orig = sampler.sample_tokens_rowwise

    def altered(keys, logits, temperature=1.0):
        out = orig(keys, logits, temperature)
        return out.at[0].set((out[0] + 1) % logits.shape[-1])
    monkeypatch.setattr(sampler, "sample_tokens_rowwise", altered)
    cell, _, r = _readings(closed, 12)
    assert r["token_gap"]["value"] > cell.limits["token_gap"]
    assert not H.judge(r, cell.limits)


def test_rewards_of_half_the_batch_replaced_is_not_correct(closed,
                                                           monkeypatch):
    _, stack, _ = closed
    orig = stack.backend._score_batch_fn

    def half(p, toks, pos, lengths):
        r = orig(p, toks, pos, lengths)
        return r.at[r.shape[0] // 2:].set(r[0])
    monkeypatch.setattr(stack.backend, "_score_batch_fn", half)
    cell, _, r = _readings(closed, 13)
    assert not H.judge(r, cell.limits), r


def test_embedding_altered_is_not_correct(closed, monkeypatch):
    _, stack, _ = closed
    orig = stack.backend._embed_batch_fn
    monkeypatch.setattr(stack.backend, "_embed_batch_fn",
                        lambda p, t, pos: orig(p, t, pos) * 1.05)
    cell, _, r = _readings(closed, 14)
    assert r["embed_gap"]["value"] > cell.limits["embed_gap"]
    assert not H.judge(r, cell.limits)


def test_float8_control_fails_every_cells_limits(closed):
    """The control (the reference in float8 in the program's place)
    must fail at least one number of the tiny cell's limits and of each
    real cell's, here at a size a test run can hold."""
    cell, _, r = _readings(closed, 15, control=True)
    control = {k: {"value": v["control"]} for k, v in r.items()}
    assert H.judge(r, cell.limits) and not H.judge(control, cell.limits)
    for f in glob.glob(os.path.join(BT.REPO, BT.DATA, "limits", "*.json")):
        assert not H.judge(control, json.load(open(f))), (f, r)


def test_measure_reports_the_cells_metrics(root):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run_cli", os.path.join(BT.REPO, BT.DATA, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    for name, trace in (("tiny-open", 0), ("tiny-closed", 1)):
        cell = H.load_cell(root, name)
        out = run.measure(cell, 2 ** 31 + 21, 1.5, trace, root=root,
                          require_tpu=False, peak=BT.PEAK)
        assert out["correct"], out
        assert list(out)[-1] == "checks"
        want = cell.per_layer if trace else cell.end_to_end
        names = {m["name"] for m in want}
        if trace:    # no device ops on the CPU: device-trace readers
            names -= {m["name"] for m in want      # find nothing
                      if m["source"] == "device_trace"}
        assert names <= set(out["metrics"]), (names, out["metrics"])
