"""Launcher CLIs exercised in subprocesses (they mutate XLA device state,
so they must not run in the test process)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _run(args, timeout=600):
    return subprocess.run(
        [sys.executable, "-m"] + args, cwd=REPO, env=ENV,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_dryrun_cli_lowers_on_production_mesh(tmp_path):
    r = _run(["repro.launch.dryrun", "--arch", "llama3.2-1b",
              "--shape", "decode_32k", "--out", str(tmp_path)])
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(tmp_path / "llama3.2-1b__decode_32k__sp.json"))
    assert rec["status"] == "ok"
    assert rec["memory"]["peak_bytes_est"] > 0
    assert rec["roofline"]["bottleneck"] in ("compute", "memory",
                                             "collective")


@pytest.mark.slow
def test_dryrun_cli_respects_skip_policy(tmp_path):
    r = _run(["repro.launch.dryrun", "--arch", "hubert-xlarge",
              "--shape", "decode_32k", "--out", str(tmp_path)])
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.load(open(tmp_path / "hubert-xlarge__decode_32k__sp.json"))
    assert rec["status"] == "skip"


def test_report_cli_runs():
    if not os.path.isdir(os.path.join(REPO, "experiments", "dryrun")):
        pytest.skip("no recorded dryruns")
    r = _run(["repro.analysis.report"], timeout=120)
    assert r.returncode == 0
    assert "Roofline" in r.stdout


_CACHE_PROBE = """
import json
import jax
from repro.launch.cache import use_compile_cache
print(json.dumps([use_compile_cache(), jax.config.jax_compilation_cache_dir]))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(tmp_path, from_env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set in
    code; without it the cache sits at the checkout's fixed
    ``.jax_cache/``."""
    env = {k: v for k, v in ENV.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=tmp_path,
                       env=dict(env, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == [want, want]


def test_build_stack_seeded_weights_keep_published_vocab():
    """``train_steps=0`` serves seeded weights at the config's own vocab
    (PRM and embedder share it), on the jnp paths off the TPU."""
    from repro.configs import get_config
    from repro.launch.serve import build_stack

    (backend,), scfg = build_stack("tiny-lm", width=4, train_steps=0)
    vocab = get_config("tiny-lm").vocab_size
    assert backend.engine.cfg.vocab_size == vocab
    assert backend.prm_model.cfg.vocab_size == vocab
    assert backend.embed_model.cfg.vocab_size == vocab
    assert backend.engine.ecfg.attention == "tree"
    assert backend.engine.ecfg.use_kernel is False
    assert scfg.method == "ets" and scfg.width == 4
