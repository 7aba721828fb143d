"""A tiny benchmark root for the CPU tests: a BENCHMARK.json with one
closed and one open cell on a 2-layer model, beside copies of the
benchmark's own metric readers, family modules and peaks table."""
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join("benchmarks", "chip")

CONFIG = {
    "name": "tiny", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "torch_dtype": "float32", "step_token": 10, "eos_token": 2,
    "prm": {"num_hidden_layers": 2},
    "embedder": {"hidden_size": 32, "intermediate_size": 64,
                 "num_hidden_layers": 2, "num_attention_heads": 2,
                 "num_key_value_heads": 2, "vocab_size": 256,
                 "hidden_act": "gelu", "rms_norm_eps": 1e-12,
                 "torch_dtype": "float32"},
    "engine": {"n_pages": 64, "page_size": 8},
}

CLOSED = {"mode": "closed", "backlog": 6, "max_live": 2, "width": 3,
          "max_steps": 3, "step_tokens": 6, "prompt_len": [12, 40],
          "temperature": 1.0,
          "ets": {"lambda_b": 2.0, "lambda_d": 1.0, "cluster_threshold": 0.15},
          "check": {"requests": 2, "prm_rows": 6, "embed_rows": 6}}

OPEN = dict(CLOSED, mode="open", rate_per_s=4.0, drain_s=60, width=2,
            max_steps=2)

LIMITS = {"token_gap": 1e-3, "prm_gap": 1e-4, "embed_gap": 1e-3}

PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


def make_root(path, limits=LIMITS):
    """Write the tiny root under ``path``; returns ``path``."""
    d = os.path.join(path, DATA)
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(d, sub), exist_ok=True)
    for sub in ("metrics", "families"):
        shutil.copytree(os.path.join(REPO, DATA, sub), os.path.join(d, sub),
                        dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, DATA, "peaks.json"), d)
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": f"{DATA}/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny-closed", "config": "tiny", "traffic": "closed",
         "chips": 1, "why": "test"},
        {"name": "tiny-open", "config": "tiny", "traffic": "open",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-open" if "open" in w else "tiny-closed"
                              for w in m["workloads"]]
    files = {"BENCHMARK.json": bench,
             f"{DATA}/configs/tiny.json": CONFIG,
             f"{DATA}/traffic/closed.json": CLOSED,
             f"{DATA}/traffic/open.json": OPEN}
    if limits is not None:
        files[f"{DATA}/limits/tiny-closed.json"] = limits
        files[f"{DATA}/limits/tiny-open.json"] = limits
    for rel, obj in files.items():
        with open(os.path.join(path, rel), "w") as f:
            json.dump(obj, f)
    return path
