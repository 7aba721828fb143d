"""Per-layer-group runtimes: the engine <-> model-family contract.

The paged engine historically assumed every layer is KV attention — the
jitted prefill/decode bodies open-coded the dense transformer layer.
This module turns that assumption into an explicit protocol: the engine
composes a stack of :class:`LayerRuntime` objects, one per homogeneous
group of ``cfg.layer_plan()``, and each jitted step threads the residual
stream (and the pools) through the stack:

  * :class:`AttentionRuntime`  — dense/VLM GQA layers.  The per-layer
    math is the historical engine body verbatim (same op order, same
    pool indexing), so the dense path through the protocol is
    bit-identical to the pre-refactor engine in both attention modes.
  * :class:`MoERuntime`        — same attention, MoE FFN (mixtral-style
    sort-dispatch; rides the lock-step decode stream unchanged).
  * :class:`RecurrentRuntime`  — mamba2 (SSD) or rwkv6 (wkv) mixers.
    Constant-size per-sequence state lives in a :class:`StatePool`
    (kvcache.pool): one state page per sequence, copy-on-branch, so
    tree search's branch/prune/swap/demote machinery works unchanged.
  * :class:`HybridRuntime`     — Zamba2: a Mamba2 layer each, and at
    every hybrid layer a shared attention+MLP block over ``[x ; x0]``
    (models/hybrid.py) whose KV goes through the paged pool (KV pool
    depth = number of hybrid layers).

Each runtime exposes three jit-traceable methods (called inside the
engine's jitted steps — arguments are tracers):

  ``decode_step(params, x, ctx, pool_k, pool_v, state)``
      one lock-step token; writes KV / recurrent state in place
      (functionally) and returns the updated residual + pools.
  ``prefill_into_pool(params, x, ctx, ...)``
      a right-padded prompt bucket; attention writes each layer's K/V
      straight into the pool pages, recurrent groups run the masked
      chunked scan (identity steps past ``ctx.lengths``) and write the
      exact post-prompt state into their state pages.
  ``prefill_streamed(params, x, ctx, ...)``
      one segment of a page-streamed long prompt; attention gathers
      history K/V from the pool through the block table, recurrent
      groups read the running state from the pool and write it back —
      a freshly allocated state page is the valid empty-history state
      (StatePool zeroes at alloc), so segment 0 needs no special case.

Decode bodies mirror ``LM.decode_step`` exactly — recurrent groups run
the same ``lax.scan`` over the same stacked group params — so the
engine's streams match the contiguous oracle per family.

State-page layout: each runtime namespaces its state tensors by group
index (``"{gi}:h"``, ``"{gi}:S"``, ...); arrays are
``(n_group_layers, n_state_pages, *per_page)`` and rows address them
through ``ctx.state_rows`` (dump page for inactive rows), mirroring how
KV rows address the paged pool through block tables.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import hybrid as HY
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R
from repro.models.layers import apply_rope, mlp_apply, rms_norm, rope_angles


# ---------------------------------------------------------------------------
# Step contexts (built inside the engine's jitted bodies; fields are tracers)
# ---------------------------------------------------------------------------

@dataclass
class DecodeCtx:
    """One lock-step decode iteration's per-row operands."""
    lengths: Any          # (B,) context length == new token's position
    pages: Any            # (B,) physical write page (dump for inactive)
    slots: Any            # (B,) in-page write slot
    state_rows: Any       # (B,) state page per row (dump for inactive)
    attend: Any           # attend(kv_layer, q (B,H,hd), pool_k, pool_v)
    x0: Any = None        # (B,1,d) input embeddings (hybrid blocks)


@dataclass
class PrefillCtx:
    """A right-padded prefill bucket (or one streamed segment, B=1)."""
    positions: Any        # (B,T) int32, -1 at padded slots
    pos: Any              # positions, or (3,B,T) for M-RoPE
    pages: Any            # (B,T) write pages (dump at padding)
    slots: Any            # (B,T) write slots
    lengths: Any          # (B,) valid tokens per row
    state_rows: Any       # (B,) state page per row
    hist_table: Any = None   # streamed only: (B,Tp) pow2-padded block table
    hist_len: Any = None     # streamed only: tokens already in the pool
    x0: Any = None           # (B,T,d) input embeddings (hybrid blocks)


# ---------------------------------------------------------------------------
# Shared attention-layer bodies (verbatim the historical engine math)
# ---------------------------------------------------------------------------

def _attn_decode_layer(cfg, blk, x, ctx, kv_l, pool_k, pool_v, ffn):
    """One attention layer of a lock-step decode (historical
    ``_decode_body`` iteration): project/rope the new token, write its
    K/V at the reserved pool slot, attend via ``ctx.attend``."""
    B = x.shape[0]
    h = rms_norm(blk["ln1"], x, cfg.norm_eps)
    ap = blk["attn"]
    hd = cfg.head_dim
    q = (h @ ap["wq"]).reshape(B, 1, cfg.n_heads, hd)
    k = (h @ ap["wk"]).reshape(B, 1, cfg.n_kv_heads, hd)
    v = (h @ ap["wv"]).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_norm(ap["q_norm"], q, cfg.norm_eps)
        k = rms_norm(ap["k_norm"], k, cfg.norm_eps)
    ang = rope_angles(ctx.lengths[:, None], hd, cfg.rope_theta, ())
    q = apply_rope(q, ang)
    k = apply_rope(k, ang)
    pool_k = pool_k.at[kv_l, ctx.pages, ctx.slots].set(k[:, 0])
    pool_v = pool_v.at[kv_l, ctx.pages, ctx.slots].set(v[:, 0])
    y = ctx.attend(kv_l, q[:, 0], pool_k, pool_v)
    x = x + (y.reshape(B, 1, -1) @ ap["wo"])
    h = rms_norm(blk["ln2"], x, cfg.norm_eps)
    return x + ffn(blk, h), pool_k, pool_v


def _prefill_attend(cfg, q, k, v, ctx, kv_l, pool_k, pool_v, *,
                    dense: bool, use_kernel: bool):
    """Write a prefill bucket's K/V into the pool pages, then attend
    within the bucket.  Returns (y (B,T,H,hd), pool_k, pool_v)."""
    scale = cfg.attn_scale
    pool_k = pool_k.at[kv_l, ctx.pages, ctx.slots].set(k.astype(pool_k.dtype))
    pool_v = pool_v.at[kv_l, ctx.pages, ctx.slots].set(v.astype(pool_v.dtype))
    if dense:
        mask = A.make_mask(ctx.positions, ctx.positions, causal=cfg.causal,
                           window=cfg.sliding_window)
        y = A.masked_attention(q, k, v, mask, scale=scale)
    elif use_kernel:
        from repro.kernels import ops
        y = ops.flash_prefill(q, k, v, scale=scale, causal=cfg.causal,
                              window=cfg.sliding_window)
    else:
        y = A.blocked_attention(q, k, v, ctx.positions, ctx.positions,
                                causal=cfg.causal, window=cfg.sliding_window,
                                scale=scale)
    return y, pool_k, pool_v


def _attn_prefill_layer(cfg, blk, x, ctx, kv_l, pool_k, pool_v, ffn, *,
                        dense: bool, use_kernel: bool):
    """One attention layer of a one-shot prefill bucket (historical
    ``_build_prefill_fn`` iteration)."""
    B, T = x.shape[:2]
    h = rms_norm(blk["ln1"], x, cfg.norm_eps)
    q, k, v = A._project_qkv(blk["attn"], h, cfg, ctx.pos)
    y, pool_k, pool_v = _prefill_attend(cfg, q, k, v, ctx, kv_l, pool_k,
                                        pool_v, dense=dense,
                                        use_kernel=use_kernel)
    x = x + y.reshape(B, T, -1) @ blk["attn"]["wo"]
    h = rms_norm(blk["ln2"], x, cfg.norm_eps)
    return x + ffn(blk, h), pool_k, pool_v


def _streamed_hist(cfg, ctx, page_size: int):
    """History gather indices + concat mask for one streamed segment
    (historical ``_build_streamed_prefill_fn`` preamble)."""
    B = ctx.positions.shape[0]
    Lh = ctx.hist_table.shape[1] * page_size
    hist_idx = (jnp.clip(ctx.hist_table, 0)[:, :, None] * page_size
                + jnp.arange(page_size)[None, None, :]).reshape(B, Lh)
    hist_pos = jnp.where(jnp.arange(Lh)[None, :] < ctx.hist_len,
                         jnp.arange(Lh)[None, :], -1)
    mask_h = A.make_mask(ctx.positions, hist_pos, causal=cfg.causal,
                         window=cfg.sliding_window)
    mask_s = A.make_mask(ctx.positions, ctx.positions, causal=cfg.causal,
                         window=cfg.sliding_window)
    return hist_idx, jnp.concatenate([mask_h, mask_s], axis=-1)


def _streamed_attend(cfg, q, k, v, ctx, kv_l, pool_k, pool_v, hist_idx,
                     mask):
    """Write a streamed segment's K/V into the pool, then attend over the
    prompt's history gathered from the pool and the segment itself.
    Returns (y (B,Ts,H,hd), pool_k, pool_v)."""
    P = pool_k.shape[1]
    ps = pool_k.shape[2]
    pool_k = pool_k.at[kv_l, ctx.pages, ctx.slots].set(k.astype(pool_k.dtype))
    pool_v = pool_v.at[kv_l, ctx.pages, ctx.slots].set(v.astype(pool_v.dtype))
    K, hd = k.shape[2], k.shape[3]
    flat_k = pool_k[kv_l].reshape(P * ps, K, hd)
    flat_v = pool_v[kv_l].reshape(P * ps, K, hd)
    hk = flat_k[hist_idx]                      # (B, Lh, K, hd)
    hv = flat_v[hist_idx]
    kk = jnp.concatenate([hk.astype(k.dtype), k], axis=1)
    vv = jnp.concatenate([hv.astype(v.dtype), v], axis=1)
    y = A.masked_attention(q, kk, vv, mask, scale=cfg.attn_scale)
    return y, pool_k, pool_v


def _attn_streamed_layer(cfg, blk, x, ctx, kv_l, pool_k, pool_v, ffn,
                         hist_idx, mask):
    """One attention layer of a page-streamed prefill segment
    (historical ``_build_streamed_prefill_fn`` iteration)."""
    B, Ts = x.shape[:2]
    h = rms_norm(blk["ln1"], x, cfg.norm_eps)
    q, k, v = A._project_qkv(blk["attn"], h, cfg, ctx.pos)
    y, pool_k, pool_v = _streamed_attend(cfg, q, k, v, ctx, kv_l, pool_k,
                                         pool_v, hist_idx, mask)
    x = x + y.reshape(B, Ts, -1) @ blk["attn"]["wo"]
    h = rms_norm(blk["ln2"], x, cfg.norm_eps)
    return x + ffn(blk, h), pool_k, pool_v


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------

class LayerRuntime:
    """One homogeneous layer group's serving behaviour.

    ``n_kv_layers`` is the group's footprint in the paged KV pool's
    layer axis (0 for pure-recurrent groups); ``state_specs()`` declares
    its StatePool tensors as ``name -> (n_layers, per_page_shape,
    dtype)``.  The three step methods are traced inside the engine's
    jitted functions.
    """

    kind: str = ""
    n_kv_layers: int = 0

    def __init__(self, model, ecfg, gi: int, count: int):
        self.model = model
        self.cfg = model.cfg
        self.gi = gi
        self.count = count

    def state_specs(self) -> Dict[str, tuple]:
        return {}

    def decode_step(self, params, x, ctx, pool_k, pool_v, state):
        raise NotImplementedError

    def prefill_into_pool(self, params, x, ctx, pool_k, pool_v, state):
        raise NotImplementedError

    def prefill_streamed(self, params, x, ctx, pool_k, pool_v, state):
        raise NotImplementedError

    # -- recurrent-state plumbing (shared by the stateful runtimes) ----
    _state_names: tuple = ()

    def _gather_state(self, state, rows):
        """Pool pages -> scan-shaped pytree {name: (L, B, ...)}."""
        return {n: state[f"{self.gi}:{n}"][:, rows] for n in self._state_names}

    def _scatter_state(self, state, rows, new):
        out = dict(state)
        for n in self._state_names:
            key = f"{self.gi}:{n}"
            out[key] = state[key].at[:, rows].set(
                new[n].astype(state[key].dtype))
        return out


class AttentionRuntime(LayerRuntime):
    """Dense/VLM GQA layers over the paged pool — the historical engine
    body, now addressed at ``kv_offset .. kv_offset+count`` in the
    pool's layer axis."""

    kind = "attn"

    def __init__(self, model, ecfg, gi: int, count: int, kv_offset: int):
        super().__init__(model, ecfg, gi, count)
        self.kv_offset = kv_offset
        self.n_kv_layers = count
        self._dense = ecfg.prefill == "dense"
        self._use_kernel = ecfg.use_kernel
        self._page_size = ecfg.page_size

    def _ffn(self, blk, h):
        return mlp_apply(blk["mlp"], h, self.cfg.act)

    def decode_step(self, params, x, ctx, pool_k, pool_v, state):
        gp = params["groups"][self.gi]
        for l in range(self.count):
            blk = jax.tree.map(lambda a: a[l], gp)
            x, pool_k, pool_v = _attn_decode_layer(
                self.cfg, blk, x, ctx, self.kv_offset + l, pool_k, pool_v,
                lambda b, h: self._ffn(b, h))
        return x, pool_k, pool_v, state

    def prefill_into_pool(self, params, x, ctx, pool_k, pool_v, state):
        gp = params["groups"][self.gi]
        for l in range(self.count):
            blk = jax.tree.map(lambda a: a[l], gp)
            x, pool_k, pool_v = _attn_prefill_layer(
                self.cfg, blk, x, ctx, self.kv_offset + l, pool_k, pool_v,
                lambda b, h: self._ffn(b, h),
                dense=self._dense, use_kernel=self._use_kernel)
        return x, pool_k, pool_v, state

    def prefill_streamed(self, params, x, ctx, pool_k, pool_v, state):
        hist_idx, mask = _streamed_hist(self.cfg, ctx, self._page_size)
        gp = params["groups"][self.gi]
        for l in range(self.count):
            blk = jax.tree.map(lambda a: a[l], gp)
            x, pool_k, pool_v = _attn_streamed_layer(
                self.cfg, blk, x, ctx, self.kv_offset + l, pool_k, pool_v,
                lambda b, h: self._ffn(b, h), hist_idx, mask)
        return x, pool_k, pool_v, state


class MoERuntime(AttentionRuntime):
    """Mixtral-style MoE layers: identical attention/KV behaviour, MoE
    FFN (sort-dispatch, models/moe.py) instead of the dense MLP.  MoE
    decode rides the lock-step decode stream unchanged — routing is
    per-token, so one jitted step serves every live branch."""

    kind = "moe"

    def _ffn(self, blk, h):
        B, T, d = h.shape
        y, _ = MOE.moe_apply_auto(blk["moe"], h.reshape(B * T, d), self.cfg)
        return y.reshape(B, T, d)


class RecurrentRuntime(LayerRuntime):
    """mamba2 / rwkv6 layer groups: no KV pages; per-sequence constant
    state in the StatePool.  Decode runs the exact ``LM.decode_step``
    scan over the same stacked group params; prefill runs the masked
    chunked scan (identity steps past ``ctx.lengths``) so right-padded
    engine buckets produce the exact post-prompt state."""

    def __init__(self, model, ecfg, gi: int, count: int, flavor: str):
        super().__init__(model, ecfg, gi, count)
        assert flavor in ("mamba", "wkv"), flavor
        self.flavor = flavor
        self.kind = flavor
        if flavor == "mamba":
            proto = M.init_mamba_state(self.cfg, 1)
        else:
            proto = R.init_rwkv_state(self.cfg, 1)
        self._proto = proto
        self._state_names = tuple(sorted(proto))

    def state_specs(self):
        return {f"{self.gi}:{n}": (self.count, v.shape[1:], v.dtype)
                for n, v in self._proto.items()}

    # -- scan bodies (mirroring LM.decode_step / LM._run_full) ---------
    def _decode_scan(self, x, gp, gstate):
        cfg = self.cfg
        if self.flavor == "wkv":
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
        else:
            def body(x, blk_state):
                blk, st = blk_state
                h = rms_norm(blk["ln"], x, cfg.norm_eps)
                y, new = M.mamba_decode_step(blk["mamba"], h, cfg, st)
                return x + y, new
        return jax.lax.scan(body, x, (gp, gstate))

    def _prefill_scan(self, x, gp, gstate, lengths):
        cfg = self.cfg
        B, T, d = x.shape
        if self.flavor == "wkv":
            def body(x, blk_state):
                blk, st = blk_state
                h = rms_norm(blk["ln1"], x, cfg.norm_eps)
                tm_state = {"S": st["S"], "x_prev": st["x_prev"]}
                y, tm_new = R.rwkv_apply_full(blk["time_mix"], h, cfg,
                                              tm_state, lengths=lengths)
                x = x + y
                h2 = rms_norm(blk["ln2"], x, cfg.norm_eps)
                shift = jnp.concatenate(
                    [st["x_prev"][:, 1:2].astype(h2.dtype), h2[:, :-1]],
                    axis=1)
                y = R.channel_mix_apply(blk["channel_mix"], h2, shift)
                # channel-mix shift state: h2 at the last valid position
                idx = jnp.clip(lengths - 1, 0)[:, None, None]
                last = jnp.take_along_axis(
                    h2, jnp.broadcast_to(idx, (B, 1, d)), axis=1)[:, 0]
                last = jnp.where((lengths > 0)[:, None], last,
                                 st["x_prev"][:, 1].astype(h2.dtype))
                new = {"S": tm_new["S"],
                       "x_prev": jnp.stack(
                           [tm_new["x_prev"][:, 0], last], axis=1)}
                return x + y, new
        else:
            def body(x, blk_state):
                blk, st = blk_state
                h = rms_norm(blk["ln"], x, cfg.norm_eps)
                y, new = M.mamba_apply_full(blk["mamba"], h, cfg, st,
                                            lengths=lengths)
                return x + y, new
        return jax.lax.scan(body, x, (gp, gstate))

    # -- protocol ------------------------------------------------------
    def decode_step(self, params, x, ctx, pool_k, pool_v, state):
        gp = params["groups"][self.gi]
        gstate = self._gather_state(state, ctx.state_rows)
        x, g_new = self._decode_scan(x, gp, gstate)
        state = self._scatter_state(state, ctx.state_rows, g_new)
        return x, pool_k, pool_v, state

    def prefill_into_pool(self, params, x, ctx, pool_k, pool_v, state):
        gp = params["groups"][self.gi]
        gstate = self._gather_state(state, ctx.state_rows)
        x, g_new = self._prefill_scan(x, gp, gstate, ctx.lengths)
        state = self._scatter_state(state, ctx.state_rows, g_new)
        return x, pool_k, pool_v, state

    # a streamed segment reads the running state from the pool and
    # writes it back — identical to a one-shot bucket (zero-at-alloc
    # pages make segment 0 the empty-history state automatically)
    prefill_streamed = prefill_into_pool


class HybridRuntime(LayerRuntime):
    """Zamba2 (models/hybrid.py): every layer a Mamba2 layer with its
    state in the StatePool, and at each hybrid layer a shared block
    over ``[x ; ctx.x0]`` served through the paged pool — KV pool layer
    ``kv_offset + j`` holds the j-th hybrid layer's KV.  The layer walk,
    block and Mamba2 mixers are ``LM``'s own."""

    kind = "hybrid"

    def __init__(self, model, ecfg, gi: int, count: int, kv_offset: int):
        super().__init__(model, ecfg, gi, count)
        self.kv_offset = kv_offset
        self.n_kv_layers = len(self.cfg.hybrid_ids())
        self._dense = ecfg.prefill == "dense"
        self._use_kernel = ecfg.use_kernel
        self._page_size = ecfg.page_size
        self._proto = M.init_mamba_state(self.cfg, 1)
        self._state_names = tuple(sorted(self._proto))
        # a page holds h as (hd, d_state, heads) and the conv tail flat:
        # the layouts the mixer computes in, so that the compiler does not
        # re-lay the whole pool out at every step
        h, conv = self._proto["h"].shape[1:], self._proto["conv"].shape[1:]
        self._page = {"h": (h[1], h[2], h[0]), "conv": (conv[0] * conv[1],)}

    def state_specs(self):
        return {f"{self.gi}:{n}": (self.count, self._page[n], v.dtype)
                for n, v in self._proto.items()}

    def _from_page(self, name, a):
        """(B, *page) -> the mixer's (B, *state) layout."""
        if name == "h":
            return jnp.moveaxis(a, -1, 1)
        return a.reshape((-1,) + self._proto[name].shape[1:])

    def _to_page(self, name, a):
        if name == "h":
            return jnp.moveaxis(a, 1, -1)
        return a.reshape((-1,) + self._page[name])

    def _run(self, params, x, ctx, pool_k, pool_v, state, positions, mixer,
             attend):
        """The layer walk over tokens at ``positions`` (B, T):
        ``attend(kv_layer, q, k, v, pool_k, pool_v) -> (y, pool_k,
        pool_v)`` is a hybrid layer's attention."""
        cfg = self.cfg
        pools = [pool_k, pool_v]

        def block(j, x):
            blk, hl = HY.block_params(params, cfg, j)
            q, k, v = HY.block_qkv(blk, x, ctx.x0, cfg, positions)
            y, pools[0], pools[1] = attend(self.kv_offset + j, q, k, v,
                                           *pools)
            return HY.block_out(blk, hl, y.reshape(x.shape[:2] + (-1,)),
                                cfg)

        # each layer reads and writes its rows' state pages inside the
        # layer scan, so one layer's states are in flight at a time
        rows, gp = ctx.state_rows, params["groups"][self.gi]
        keys = {n: f"{self.gi}:{n}" for n in self._state_names}
        state = dict(state)

        def mamba(x, a, b, t):
            layer = HY.mamba_layer(cfg, mixer, t)

            def body(carry, inp):
                x, st = carry
                blk, l = inp
                x, new = layer(x, (blk, {n: self._from_page(n, st[k][l, rows])
                                         for n, k in keys.items()}))
                return (x, {k: st[k].at[l, rows].set(self._to_page(
                    n, new[n]).astype(st[k].dtype))
                    for n, k in keys.items()}), None

            (x, st), _ = jax.lax.scan(
                body, (x, {k: state[k] for k in keys.values()}),
                (jax.tree.map(lambda arr: arr[a:b], gp), jnp.arange(a, b)))
            state.update(st)
            return x

        x = HY.run_layers(cfg, x, mamba, block)
        return x, pools[0], pools[1], state

    def decode_step(self, params, x, ctx, pool_k, pool_v, state):
        cfg = self.cfg

        def attend(kv_l, q, k, v, pk, pv):
            pk = pk.at[kv_l, ctx.pages, ctx.slots].set(k[:, 0])
            pv = pv.at[kv_l, ctx.pages, ctx.slots].set(v[:, 0])
            return ctx.attend(kv_l, q[:, 0], pk, pv), pk, pv

        return self._run(params, x, ctx, pool_k, pool_v, state,
                         ctx.lengths[:, None],
                         lambda mp, h, st: M.mamba_decode_step(mp, h, cfg, st),
                         attend)

    def _prefill_mixer(self, ctx):
        cfg = self.cfg
        return lambda mp, h, st: M.mamba_apply_full(mp, h, cfg, st,
                                                    lengths=ctx.lengths)

    def prefill_into_pool(self, params, x, ctx, pool_k, pool_v, state):
        def attend(kv_l, q, k, v, pk, pv):
            return _prefill_attend(self.cfg, q, k, v, ctx, kv_l, pk, pv,
                                   dense=self._dense,
                                   use_kernel=self._use_kernel)
        return self._run(params, x, ctx, pool_k, pool_v, state,
                         ctx.positions, self._prefill_mixer(ctx), attend)

    def prefill_streamed(self, params, x, ctx, pool_k, pool_v, state):
        hist_idx, mask = _streamed_hist(self.cfg, ctx, self._page_size)

        def attend(kv_l, q, k, v, pk, pv):
            return _streamed_attend(self.cfg, q, k, v, ctx, kv_l, pk, pv,
                                    hist_idx, mask)
        return self._run(params, x, ctx, pool_k, pool_v, state,
                         ctx.positions, self._prefill_mixer(ctx), attend)


# ---------------------------------------------------------------------------
# Stack builder
# ---------------------------------------------------------------------------

def build_runtimes(model, ecfg):
    """One LayerRuntime per ``cfg.layer_plan()`` group, with KV pool
    layer offsets assigned in plan order."""
    cfg = model.cfg
    runtimes = []
    kv_offset = 0
    for gi, (kind, count) in enumerate(cfg.layer_plan()):
        if kind == "attn":
            cls = MoERuntime if cfg.arch_type == "moe" else AttentionRuntime
            rt = cls(model, ecfg, gi, count, kv_offset)
            kv_offset += rt.n_kv_layers
        elif kind in ("wkv", "mamba"):
            rt = RecurrentRuntime(model, ecfg, gi, count, flavor=kind)
        elif kind == "hybrid":
            rt = HybridRuntime(model, ecfg, gi, count, kv_offset)
            kv_offset += rt.n_kv_layers
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        runtimes.append(rt)
    return runtimes


def total_kv_layers(runtimes) -> int:
    return sum(rt.n_kv_layers for rt in runtimes)


def collect_state_specs(runtimes) -> Dict[str, tuple]:
    specs: Dict[str, tuple] = {}
    for rt in runtimes:
        specs.update(rt.state_specs())
    return specs
