"""Training substrate: optimizer math, synthetic task, checkpointing,
and short end-to-end fits."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.model import build_model
from repro.training import (AdamWConfig, ArithmeticTask, TrainConfig,
                            adamw_init, adamw_update, cosine_lr, train_lm,
                            train_prm)
from repro.training import checkpoint
from repro.training.task import VOCAB_SIZE, decode, encode


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_cosine_schedule():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    assert float(cosine_lr(cfg, 0)) == 0.0
    assert abs(float(cosine_lr(cfg, 10)) - 1e-3) < 1e-9
    assert abs(float(cosine_lr(cfg, 100)) - 1e-4) < 1e-6
    assert float(cosine_lr(cfg, 55)) > float(cosine_lr(cfg, 90))


def test_adamw_reduces_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100,
                      weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.05


def test_adamw_grad_clip():
    cfg = AdamWConfig(lr=0.1, grad_clip=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(3)}
    state = adamw_init(params)
    huge = {"w": jnp.full(3, 1e9)}
    params2, _ = adamw_update(cfg, params, huge, state)
    assert float(jnp.abs(params2["w"]).max()) < 1.0  # clipped step


# ---------------------------------------------------------------------------
# Task
# ---------------------------------------------------------------------------

def test_task_roundtrip_and_oracle():
    task = ArithmeticTask(n_ops=3)
    rng = np.random.default_rng(0)
    prompt, steps, ans = task.sample_problem(rng)
    text = prompt + "".join(steps) + f"A{ans}\n"
    toks = encode(text)
    assert decode(toks) == text
    assert task.extract_answer(toks) == ans
    assert task.check_trajectory(toks)
    # corrupt a step result -> oracle rejects
    bad = text.replace(steps[1], steps[1][:-2] +
                       str((int(steps[1][-2]) + 3) % 10) + "\n")
    assert not task.check_trajectory(encode(bad))


def test_prm_labels_flip_after_corruption():
    task = ArithmeticTask(n_ops=3)
    rng = np.random.default_rng(3)
    for _ in range(10):
        b = task.prm_batch(rng, 1, corrupt_p=1.0)
        lab = b["labels"][0][b["loss_mask"][0] > 0]
        # monotone: once wrong, stays wrong
        assert (np.diff(lab) <= 0).all()
        assert lab[-1] == 0.0


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.ones((3, 4)), "b": [jnp.zeros(2), jnp.arange(5)],
            "c": {"d": jnp.asarray(2.0)}}
    path = os.path.join(tmp_path, "ckpt.npz")
    checkpoint.save(path, tree)
    like = jax.tree.map(jnp.zeros_like, tree)
    out = checkpoint.load(path, like)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Short fits (loss decreases)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_lm_short_fit():
    task = ArithmeticTask(n_ops=2, seq_len=48)
    cfg = dataclasses.replace(get_config("tiny-lm"), vocab_size=VOCAB_SIZE,
                              n_layers=2, d_model=128, n_heads=4,
                              n_kv_heads=2, d_ff=256)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.key(0))
    _, hist = train_lm(model, params, task,
                       TrainConfig(steps=60, batch=16, log_every=30))
    assert hist[-1] < hist[0] * 0.75


@pytest.mark.slow
def test_prm_short_fit():
    task = ArithmeticTask(n_ops=2, seq_len=48)
    cfg = dataclasses.replace(get_config("tiny-lm"), vocab_size=VOCAB_SIZE,
                              n_layers=2, d_model=128, n_heads=4,
                              n_kv_heads=2, d_ff=256)
    model = build_model(cfg, with_value_head=True, remat=False)
    params = model.init(jax.random.key(1))
    _, hist = train_prm(model, params, task,
                        TrainConfig(steps=60, batch=16, log_every=30))
    assert hist[-1] < hist[0]


@pytest.mark.parametrize("fit", [train_lm, train_prm])
def test_zero_steps_allocates_no_optimizer_state(fit, monkeypatch):
    """``steps=0`` serves the given weights untouched and never builds
    AdamW state (which would double device memory at published widths)."""
    from repro.training import train as train_mod

    def no_opt_state(params):
        raise AssertionError("optimizer state allocated for 0 steps")

    monkeypatch.setattr(train_mod, "adamw_init", no_opt_state)
    params = {"w": jnp.ones((3,))}
    out, hist = fit(None, params, ArithmeticTask(n_ops=2, seq_len=48),
                    TrainConfig(steps=0))
    assert out is params and hist == []
