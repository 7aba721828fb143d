"""Unified language model covering every assigned architecture family.

``LM(cfg)`` builds a functional model (params = nested dict pytree) with:

  * ``init(key)``                                   — parameter init
  * ``forward(params, batch)``                      — full-seq logits (train)
  * ``loss(params, batch)``                         — CE loss (+ MoE aux)
  * ``prefill(params, batch, cache_len)``           — logits + KV/state cache
  * ``decode_step(params, tok, cache, pos)``        — one-token serve step
  * ``init_cache(batch, cache_len)``                — empty cache pytree
  * ``reward(params, batch)``                       — PRM scalar head (opt.)

Layer stacks are grouped by ``cfg.layer_plan()`` and each homogeneous group
is evaluated with ``lax.scan`` over stacked parameters so HLO size (and
SPMD-partitioning time on the 512-device dry-run mesh) is O(1) in depth.
Training scans wrap the body in ``jax.checkpoint`` so only the residual
stream is saved between layers.

Family specifics:
  dense/vlm/encoder — GQA attention (+ M-RoPE for VLM, bidirectional for
      encoder) + (Sw)iGLU/GELU MLP.
  moe     — GQA attention + sort-dispatch MoE FFN (models/moe.py).
  ssm     — RWKV6 time-mix + channel-mix (models/rwkv6.py).
  hybrid  — Zamba2: a Mamba2 layer each; at the hybrid layers a shared
      attention+MLP block over [x ; x0] feeds the Mamba2 input
      (models/hybrid.py).

Modality frontends (audio/VLM) are stubs per the assignment: inputs carry
precomputed frame/patch embeddings (``batch["embeds"]``) which a linear
projector maps to d_model.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import attention as A
from . import hybrid as HY
from . import mamba2 as M
from . import moe as MOE
from . import rwkv6 as R
from .layers import (dense_init, embed_init, mlp_apply, mlp_init, rms_norm,
                     softmax_cross_entropy)

Params = Dict[str, Any]

# Set by the launch layer: PartitionSpec tuple for the residual stream
# (B, S, d), e.g. (("data",), None, "model").  The layer scan's saved
# carries (the dominant train-time activation memory) inherit this — with
# d sharded on `model` the per-device residual checkpoint shrinks by the
# model-axis size (Megatron-style sequence/activation partitioning, which
# GSPMD turns into all-gather + reduce-scatter around each layer).
ACT_SHARDING = None


def _constrain_act(x):
    if ACT_SHARDING is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*ACT_SHARDING))


def _stack_init(fn, key, n: int):
    """Stack n param pytrees along a new leading axis."""
    keys = jax.random.split(key, n)
    trees = [fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class LM:
    def __init__(self, cfg, *, long_mode: bool = False,
                 with_value_head: bool = False, remat: bool = True,
                 quant_kv: bool = False):
        self.cfg = cfg
        self.long_mode = long_mode
        self.with_value_head = with_value_head
        self.remat = remat
        self.quant_kv = quant_kv   # int8 KV decode cache (§Perf)
        self.plan = cfg.layer_plan()
        self.compute_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" \
            else jnp.float32

    # ------------------------------------------------------------------
    @property
    def window(self) -> int:
        """Effective attention window (0 = unlimited)."""
        cfg = self.cfg
        if self.long_mode and cfg.long_context_window:
            return cfg.long_context_window
        return cfg.sliding_window

    def attn_cache_len(self, seq_len: int) -> int:
        """Cache length an attention layer actually needs for `seq_len`."""
        w = self.window
        return min(seq_len, w) if w else seq_len

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        ks = iter(jax.random.split(key, 16))
        dt = jnp.float32  # master params fp32; cast at apply time
        p: Params = {"embed": embed_init(next(ks), cfg.vocab_size,
                                         cfg.d_model, dt)}
        if cfg.frontend_dim:
            p["frontend_proj"] = dense_init(next(ks), cfg.frontend_dim,
                                            cfg.d_model, dt)

        def attn_block(k):
            k1, k2, k3, k4 = jax.random.split(k, 4)
            blk = {"ln1": jnp.ones((cfg.d_model,), dt),
                   "attn": A.attn_init(k1, cfg, dt),
                   "ln2": jnp.ones((cfg.d_model,), dt)}
            if cfg.arch_type == "moe":
                blk["moe"] = MOE.moe_init(k2, cfg, dt)
            else:
                blk["mlp"] = mlp_init(k3, cfg.d_model, cfg.d_ff, cfg.act, dt)
            return blk

        def wkv_block(k):
            k1, k2 = jax.random.split(k)
            return {"ln1": jnp.ones((cfg.d_model,), dt),
                    "time_mix": R.rwkv_init(k1, cfg, dt),
                    "ln2": jnp.ones((cfg.d_model,), dt),
                    "channel_mix": R.channel_mix_init(k2, cfg, dt)}

        def mamba_block(k):
            return {"ln": jnp.ones((cfg.d_model,), dt),
                    "mamba": M.mamba_init(k, cfg, dt)}

        groups = []
        for kind, count in self.plan:
            if kind == "attn":
                groups.append(_stack_init(attn_block, next(ks), count))
            elif kind == "wkv":
                groups.append(_stack_init(wkv_block, next(ks), count))
            elif kind in ("mamba", "hybrid"):
                groups.append(_stack_init(mamba_block, next(ks), count))
            else:
                raise ValueError(kind)
        p["groups"] = groups
        if cfg.hybrid_ids():
            p["shared"] = _stack_init(
                lambda k: HY.init_shared(k, cfg, dt), next(ks),
                cfg.n_mem_blocks)
            p["hybrid"] = _stack_init(
                lambda k: HY.init_hybrid_layer(k, cfg, dt), next(ks),
                len(cfg.hybrid_ids()))
        p["ln_f"] = jnp.ones((cfg.d_model,), dt)
        if not cfg.tie_embeddings:
            p["lm_head"] = dense_init(next(ks), cfg.d_model, cfg.vocab_size, dt)
        if self.with_value_head:
            p["value_head"] = dense_init(next(ks), cfg.d_model, 1, dt)
        return p

    # ------------------------------------------------------------------
    # Param casting: master params stay fp32 (train); compute in bf16.
    # The cast is differentiable, so grads flow to the fp32 masters.
    # ------------------------------------------------------------------
    def cast_params(self, p: Params) -> Params:
        cdt = self.compute_dtype

        def cast(x):
            if jnp.issubdtype(x.dtype, jnp.floating) and x.dtype != cdt:
                return x.astype(cdt)
            return x

        return jax.tree.map(cast, p)

    # ------------------------------------------------------------------
    # Input embedding
    # ------------------------------------------------------------------
    def embed_inputs(self, p: Params, batch: Dict[str, Any]):
        """Returns (x (B,S,d), positions)."""
        cfg = self.cfg
        cdt = self.compute_dtype
        parts = []
        if "embeds" in batch and batch["embeds"] is not None:
            fe = batch["embeds"].astype(cdt) @ p["frontend_proj"].astype(cdt)
            parts.append(fe)
        if "tokens" in batch and batch["tokens"] is not None:
            parts.append(p["embed"].astype(cdt)[batch["tokens"]])
        x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        B, S = x.shape[:2]
        positions = batch.get("positions")
        if positions is None:
            pos1 = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(pos1, (3, B, S))
            else:
                positions = pos1
        return x, positions

    def logits(self, p: Params, x):
        cdt = self.compute_dtype
        x = rms_norm(p["ln_f"], x, self.cfg.norm_eps)
        head = p["embed"].T if self.cfg.tie_embeddings else p["lm_head"]
        return x @ head.astype(cdt)

    # ------------------------------------------------------------------
    # Layer bodies (full-sequence)
    # ------------------------------------------------------------------
    def _attn_layer_full(self, blk, x, positions, *, build_cache=None):
        """Dense/MoE transformer layer, full sequence.

        build_cache: None (train) or cache_len (prefill -> returns cache).
        """
        cfg = self.cfg
        h = rms_norm(blk["ln1"], x, cfg.norm_eps)
        if build_cache is None:
            y = A.attn_full(blk["attn"], h, cfg, positions,
                            window_override=self.window)
            cache = None
        else:
            y, cache = A.attn_prefill(blk["attn"], h, cfg, positions,
                                      build_cache,
                                      cache_dtype=self.compute_dtype)
        x = x + y
        h = rms_norm(blk["ln2"], x, cfg.norm_eps)
        aux = 0.0
        if cfg.arch_type == "moe":
            B, S, d = h.shape
            y, aux = MOE.moe_apply_auto(blk["moe"], h.reshape(B * S, d), cfg)
            y = y.reshape(B, S, d)
        else:
            y = mlp_apply(blk["mlp"], h, cfg.act)
        return x + y, cache, aux

    def _wkv_layer_full(self, blk, x, state):
        cfg = self.cfg
        h = rms_norm(blk["ln1"], x, cfg.norm_eps)
        tm_state = {"S": state["S"], "x_prev": state["x_prev"]}
        y, tm_new = R.rwkv_apply_full(blk["time_mix"], h, cfg, tm_state)
        x = x + y
        h = rms_norm(blk["ln2"], x, cfg.norm_eps)
        # channel-mix token shift uses the *normed* stream's previous token
        shift = jnp.concatenate(
            [state["x_prev"][:, 1:2].astype(h.dtype), h[:, :-1]], axis=1)
        y = R.channel_mix_apply(blk["channel_mix"], h, shift)
        new_state = {"S": tm_new["S"],
                     "x_prev": jnp.stack(
                         [tm_new["x_prev"][:, 0], h[:, -1]], axis=1)}
        return x + y, new_state

    def _mamba_layer_full(self, blk, x, state):
        cfg = self.cfg
        h = rms_norm(blk["ln"], x, cfg.norm_eps)
        y, new_state = M.mamba_apply_full(blk["mamba"], h, cfg, state)
        return x + y, new_state

    def _hybrid_full(self, p, gp, x, positions, states, cache_len, ckpt):
        """The hybrid layer walk over a whole sequence.  Returns (x,
        new Mamba2 states, per-hybrid-layer KV caches or None)."""
        cfg = self.cfg
        x0 = x
        pos2d = positions if positions.ndim == 2 else positions[0]
        kvs = []

        def block(j, x):
            blk, hl = HY.block_params(p, cfg, j)
            q, k, v = HY.block_qkv(blk, x, x0, cfg, pos2d)
            if x.shape[1] >= A.BLOCKED_ATTN_THRESHOLD:
                y = A.blocked_attention(q, k, v, pos2d, pos2d, causal=True,
                                        window=self.window,
                                        scale=cfg.attn_scale)
            else:
                mask = A.make_mask(pos2d, pos2d, causal=True,
                                   window=self.window)
                y = A.masked_attention(q, k, v, mask, scale=cfg.attn_scale)
            if cache_len is not None:
                kvs.append(A.prefill_cache(
                    _with_window(cfg, self.window), k, v, pos2d, cache_len,
                    self.compute_dtype))
            return HY.block_out(blk, hl, y.reshape(x.shape[:2] + (-1,)), cfg)

        x, new = _hybrid_walk(
            cfg, gp, states, x, block,
            lambda mp, h, st: M.mamba_apply_full(mp, h, cfg, st), ckpt)
        kv = jax.tree.map(lambda *a: jnp.stack(a), *kvs) if kvs else None
        return _constrain_act(x), new, kv

    # ------------------------------------------------------------------
    # Full-sequence pass (train / prefill)
    # ------------------------------------------------------------------
    def _run_full(self, p: Params, x, positions, *, cache_len: Optional[int],
                  init_states=None, remat: bool = False):
        """Returns (x, caches_per_group, total_aux)."""
        cfg = self.cfg
        B, S, _ = x.shape
        caches = []
        aux_total = 0.0
        ckpt = (lambda f: jax.checkpoint(f)) if remat else (lambda f: f)
        attn_clen = None if cache_len is None else self.attn_cache_len(cache_len)

        for gi, (kind, count) in enumerate(self.plan):
            gp = p["groups"][gi]
            gstate = None if init_states is None else init_states[gi]
            if kind == "attn":
                @ckpt
                def body(carry, blk):
                    x, aux = carry
                    x, cache, a = self._attn_layer_full(
                        blk, x, positions, build_cache=attn_clen)
                    return (_constrain_act(x), aux + a), cache

                (x, aux_total), cache = jax.lax.scan(
                    body, (x, aux_total), gp)
                caches.append(cache)  # pytree stacked (L, ...) or None
            elif kind == "wkv":
                if gstate is None:
                    gstate = _stack_states(
                        lambda: R.init_rwkv_state(cfg, B), count)

                @ckpt
                def body(x, blk_state):
                    blk, st = blk_state
                    x, new = self._wkv_layer_full(blk, x, st)
                    return _constrain_act(x), new

                x, new_states = jax.lax.scan(body, x, (gp, gstate))
                caches.append(new_states)
            elif kind == "mamba":
                if gstate is None:
                    gstate = _stack_states(
                        lambda: M.init_mamba_state(cfg, B), count)

                @ckpt
                def body(x, blk_state):
                    blk, st = blk_state
                    x, new = self._mamba_layer_full(blk, x, st)
                    return _constrain_act(x), new

                x, new_states = jax.lax.scan(body, x, (gp, gstate))
                caches.append(new_states)
            elif kind == "hybrid":
                if gstate is None:
                    gstate = _stack_states(
                        lambda: M.init_mamba_state(cfg, B), count)
                x, m_new, kv = self._hybrid_full(
                    p, gp, x, positions, gstate, attn_clen, ckpt)
                caches.append({"mamba": m_new, "attn": kv})
            else:
                raise ValueError(kind)
        return x, caches, aux_total

    # ------------------------------------------------------------------
    # Public: train forward / loss
    # ------------------------------------------------------------------
    def forward(self, p: Params, batch: Dict[str, Any]):
        p = self.cast_params(p)
        x, positions = self.embed_inputs(p, batch)
        x, _, aux = self._run_full(p, x, positions, cache_len=None,
                                   remat=self.remat)
        return self.logits(p, x), aux

    def loss(self, p: Params, batch: Dict[str, Any]):
        logits, aux = self.forward(p, batch)
        labels = batch["labels"]
        # align: logits for positions covering the label span (suffix)
        if logits.shape[1] != labels.shape[1]:
            logits = logits[:, -labels.shape[1]:]
        ce = softmax_cross_entropy(logits, labels, batch.get("loss_mask"))
        lb = self.cfg.moe.load_balance_coef if self.cfg.moe else 0.0
        return ce + lb * aux

    def hidden(self, p: Params, batch: Dict[str, Any]):
        """Final-layer hidden states (B, S, d) — embedder / probing API."""
        p = self.cast_params(p)
        x, positions = self.embed_inputs(p, batch)
        x, _, _ = self._run_full(p, x, positions, cache_len=None, remat=False)
        return rms_norm(p["ln_f"], x, self.cfg.norm_eps)

    def reward(self, p: Params, batch: Dict[str, Any]):
        """PRM: per-position scalar scores (B, S).  A hybrid scores its
        rows in slices (``HY.row_slice``), each row being independent."""
        assert self.with_value_head
        p = self.cast_params(p)
        x, positions = self.embed_inputs(p, batch)

        def score(x, positions):
            x, _, _ = self._run_full(p, x, positions, cache_len=None,
                                     remat=False)
            x = rms_norm(p["ln_f"], x, self.cfg.norm_eps)
            v = (x @ p["value_head"].astype(x.dtype))[..., 0]
            return jax.nn.sigmoid(v.astype(jnp.float32))

        B, S = x.shape[:2]
        n = HY.row_slice(self.cfg, B, S) if self.cfg.arch_type == "hybrid" \
            else B
        if n == B:
            return score(x, positions)
        out = jax.lax.map(lambda a: score(*a),
                          (x.reshape((B // n, n) + x.shape[1:]),
                           positions.reshape(B // n, n, S)))
        return out.reshape(B, S)

    # ------------------------------------------------------------------
    # Public: prefill
    # ------------------------------------------------------------------
    def prefill(self, p: Params, batch: Dict[str, Any], cache_len: int):
        """Returns (last-token logits (B,V), cache)."""
        p = self.cast_params(p)
        x, positions = self.embed_inputs(p, batch)
        x, caches, _ = self._run_full(p, x, positions, cache_len=cache_len)
        pos2d = positions if positions.ndim == 2 else positions[0]
        cache = {"groups": caches,
                 "next_pos": pos2d[:, -1] + 1}
        return self.logits(p, x[:, -1]), cache

    # ------------------------------------------------------------------
    # Public: cache init + decode
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, cache_len: int):
        cfg = self.cfg
        clen = self.attn_cache_len(cache_len)
        caches = []
        for kind, count in self.plan:
            if kind == "attn":
                c = _stack_states(
                    lambda: A.init_kv_cache(cfg, batch, clen,
                                            self.compute_dtype,
                                            quant=self.quant_kv), count)
                caches.append(c)
            elif kind == "wkv":
                caches.append(_stack_states(
                    lambda: R.init_rwkv_state(cfg, batch), count))
            elif kind == "mamba":
                caches.append(_stack_states(
                    lambda: M.init_mamba_state(cfg, batch), count))
            elif kind == "hybrid":
                n_kv = len(cfg.hybrid_ids())
                caches.append({
                    "mamba": _stack_states(
                        lambda: M.init_mamba_state(cfg, batch), count),
                    "attn": _stack_states(
                        lambda: A.init_kv_cache(cfg, batch, clen,
                                                self.compute_dtype,
                                                quant=self.quant_kv), n_kv)
                    if n_kv else None,
                })
        return {"groups": caches,
                "next_pos": jnp.zeros((batch,), jnp.int32)}

    def decode_step(self, p: Params, tokens, cache, write_pos=None):
        """One-token decode.  tokens (B,1) -> (logits (B,V), new cache)."""
        cfg = self.cfg
        cdt = self.compute_dtype
        p = self.cast_params(p)
        if write_pos is None:
            write_pos = cache["next_pos"]
        x = p["embed"].astype(cdt)[tokens]              # (B,1,d)
        new_caches = []
        for gi, (kind, count) in enumerate(self.plan):
            gp = p["groups"][gi]
            gc = cache["groups"][gi]
            if kind == "attn":
                def body(x, blk_cache):
                    blk, c = blk_cache
                    h = rms_norm(blk["ln1"], x, cfg.norm_eps)
                    y, c2 = self._attn_decode(blk["attn"], h, c, write_pos)
                    x = x + y
                    h = rms_norm(blk["ln2"], x, cfg.norm_eps)
                    if cfg.arch_type == "moe":
                        B = h.shape[0]
                        y, _ = MOE.moe_apply_auto(blk["moe"],
                                             h.reshape(B, -1), cfg)
                        y = y.reshape(B, 1, -1)
                    else:
                        y = mlp_apply(blk["mlp"], h, cfg.act)
                    return x + y, c2

                x, c_new = jax.lax.scan(body, x, (gp, gc))
                new_caches.append(c_new)
            elif kind == "wkv":
                def body(x, blk_state):
                    blk, st = blk_state
                    h = rms_norm(blk["ln1"], x, cfg.norm_eps)
                    y, tm_new = R.rwkv_decode_step(blk["time_mix"], h, cfg, st)
                    x = x + y
                    h = rms_norm(blk["ln2"], x, cfg.norm_eps)
                    shift = st["x_prev"][:, 1:2].astype(h.dtype)
                    y = R.channel_mix_apply(blk["channel_mix"], h, shift)
                    new = {"S": tm_new["S"],
                           "x_prev": jnp.stack(
                               [tm_new["x_prev"][:, 0], h[:, 0]], axis=1)}
                    return x + y, new

                x, c_new = jax.lax.scan(body, x, (gp, gc))
                new_caches.append(c_new)
            elif kind == "mamba":
                def body(x, blk_state):
                    blk, st = blk_state
                    h = rms_norm(blk["ln"], x, cfg.norm_eps)
                    y, new = M.mamba_decode_step(blk["mamba"], h, cfg, st)
                    return x + y, new

                x, c_new = jax.lax.scan(body, x, (gp, gc))
                new_caches.append(c_new)
            elif kind == "hybrid":
                x0, kv_new = x, []
                wcfg = _with_window(cfg, self.window)

                def block(j, x):
                    blk, hl = HY.block_params(p, cfg, j)
                    q, k, v = HY.block_qkv(blk, x, x0, cfg, write_pos[:, None])
                    c = jax.tree.map(lambda a: a[j], gc["attn"])
                    y, c2 = A.decode_cache_attend(wcfg, q, k, v, c, write_pos,
                                                  scale=cfg.attn_scale)
                    kv_new.append(c2)
                    return HY.block_out(blk, hl, y.reshape(x.shape[0], 1, -1),
                                        cfg)

                x, m_new = _hybrid_walk(
                    cfg, gp, gc["mamba"], x, block,
                    lambda mp, h, st: M.mamba_decode_step(mp, h, cfg, st))
                new_caches.append({
                    "mamba": m_new,
                    "attn": jax.tree.map(lambda *a: jnp.stack(a), *kv_new)
                    if kv_new else None})
        logits = self.logits(p, x[:, 0])
        return logits, {"groups": new_caches, "next_pos": write_pos + 1}

    def _attn_decode(self, ap, h, c, write_pos):
        """Decode wrapper honouring the effective window."""
        cfg = self.cfg
        if self.window and not cfg.sliding_window:
            # long-mode override: pretend cfg has the window for masking
            cfg = _with_window(cfg, self.window)
        return A.attn_decode(ap, h, cfg, c, write_pos)


def _hybrid_walk(cfg, group, states, x, block, mixer, ckpt=lambda f: f):
    """``HY.run_layers`` over the stacked layer params ``group`` and
    states ``states``; returns (x, every layer's new state, stacked)."""
    news = []

    def mamba(x, a, b, t):
        sl = (lambda arr: arr[a:b])
        x, new = jax.lax.scan(ckpt(HY.mamba_layer(cfg, mixer, t)), x,
                              (jax.tree.map(sl, group),
                               jax.tree.map(sl, states)))
        news.append(new)
        return x

    x = HY.run_layers(cfg, x, mamba, block)
    return x, jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *news)


def _with_window(cfg, window: int):
    import dataclasses
    return dataclasses.replace(cfg, sliding_window=window)


def _stack_states(fn, n: int):
    trees = [fn() for _ in range(n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# Convenience
# ---------------------------------------------------------------------------

def build_model(cfg, **kw) -> LM:
    return LM(cfg, **kw)
