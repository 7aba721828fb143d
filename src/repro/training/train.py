"""Single-host training loops for the LM, the PRM and the embedder.

The *distributed* train step (pjit over the production mesh) lives in
repro/launch/train.py; this module is the CPU-runnable substrate the
end-to-end example and tests use, built on the same LM/loss/optimizer
pieces.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .optimizer import AdamWConfig, adamw_init, adamw_update


@dataclass
class TrainConfig:
    steps: int = 300
    batch: int = 32
    log_every: int = 50
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)


def prm_loss_fn(model, params, batch) -> jnp.ndarray:
    """BCE between per-position reward and prefix-correctness labels."""
    r = model.reward(params, {"tokens": batch["tokens"]})
    y = batch["labels"]
    m = batch["loss_mask"]
    eps = 1e-6
    bce = -(y * jnp.log(r + eps) + (1 - y) * jnp.log(1 - r + eps))
    return jnp.sum(bce * m) / jnp.maximum(jnp.sum(m), 1.0)


def _fit(model, params, make_batch, loss_fn, tcfg: TrainConfig,
         log_prefix: str) -> Tuple[dict, list]:
    if tcfg.steps <= 0:
        # no optimizer state: at published widths AdamW's two moments
        # would double the weights' device memory for nothing
        return params, []
    opt_state = adamw_init(params)
    opt_cfg = dataclasses.replace(tcfg.opt, total_steps=tcfg.steps)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, batch))(params)
        params, opt_state = adamw_update(opt_cfg, params, grads, opt_state)
        return params, opt_state, loss

    rng = np.random.default_rng(0)
    history = []
    t0 = time.time()
    for i in range(tcfg.steps):
        batch = {k: jnp.asarray(v) for k, v in make_batch(rng).items()}
        params, opt_state, loss = step(params, opt_state, batch)
        if i % tcfg.log_every == 0 or i == tcfg.steps - 1:
            l = float(loss)
            history.append(l)
            print(f"[{log_prefix}] step {i:4d} loss {l:.4f} "
                  f"({time.time() - t0:.1f}s)")
    return params, history


def train_lm(model, params, task, tcfg: TrainConfig):
    """Next-token CE on teacher-forced solutions."""
    def loss_fn(m, p, b):
        return m.loss(p, b)

    return _fit(model, params, lambda rng: task.lm_batch(rng, tcfg.batch),
                loss_fn, tcfg, "lm")


def train_prm(model, params, task, tcfg: TrainConfig):
    """BCE prefix-correctness on mixed correct/corrupted trajectories."""
    return _fit(model, params, lambda rng: task.prm_batch(rng, tcfg.batch),
                prm_loss_fn, tcfg, "prm")
