"""A configuration, a traffic mix and a per-layer metric are added as
new files and entries only, and the harness finds them by name."""
import json
import os

import bench_tiny as BT
from benchmarks.chip import harness as H


def test_new_config_mix_and_metric_are_found(tmp_path):
    root = BT.make_root(str(tmp_path))
    d = os.path.join(root, BT.DATA)
    cfg = dict(BT.CONFIG, name="tiny-wide", hidden_size=96)
    json.dump(cfg, open(os.path.join(d, "configs", "tiny-wide.json"), "w"))
    mix = dict(BT.CLOSED, width=5)
    json.dump(mix, open(os.path.join(d, "traffic", "closed-w5.json"), "w"))
    with open(os.path.join(d, "metrics", "prefill_tokens.new.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx['counters']['n_prefill_tokens'] or None\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": f"{BT.DATA}/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide-w5", "config": "tiny-wide",
                               "traffic": "closed-w5", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "prefill_tokens.new", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "problems_per_s", "workloads": ["tiny-wide-w5"]})
    for m in bench["end_to_end"]:
        if m["name"] == "problems_per_s":
            m["workloads"].append("tiny-wide-w5")
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))

    cell = H.load_cell(root, "tiny-wide-w5")
    assert cell.config["hidden_size"] == 96 and cell.mix["width"] == 5
    assert {m["name"] for m in cell.end_to_end} == {"problems_per_s",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["prefill_tokens.new"]
    read = H.metric_reader(root, "prefill_tokens.new")
    assert read({"counters": {"n_prefill_tokens": 7}}) == 7
    assert H.dims(cell)["generator"].d_model == 96
    # the cells already there are unchanged
    assert H.load_cell(root, "tiny-closed").config["hidden_size"] == 64
