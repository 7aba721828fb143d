"""Pallas TPU kernel: tree attention for tree-structured KV sharing.

DeFT (Yao et al., 2024) adapted to TPU: during tree search many leaves
share prefix KV segments.  Per-sequence paged attention would stream a
shared page once *per descendant leaf*; this kernel makes the unique page
the unit of work — the grid walks the unique pages of the whole tree, each
page is loaded HBM->VMEM exactly **once** and attended against every
leaf's query simultaneously, masked by a per-page descendant bitmap.
Flash-style running (m, l, acc) scratch for *all* leaves persists in VMEM
across the grid.

IO: per decode step the tree's unique KV tokens are read once, instead of
once per leaf — the kernel-level realization of the KV-sharing the ETS
cost model optimizes for (the paper defers this to DeFT; here it is
first-class).

Inputs:
  q          (B, H, hd)    — one query per live leaf
  k/v_pool   (P, S, K, hd) — the paged pool (single layer)
  page_list  (N,) int32    — unique pages of the tree (scalar prefetch)
  page_mask  (N, B) int8   — leaf b descends from page n
  page_lens  (N,) int32    — valid slots in each page
Returns (B, H, hd).

Two-level grid: ``(B // block_b, N)`` — leaf-tile-major, page-minor.
The TPU grid is sequential in the trailing axis, so for each leaf tile
the page axis sweeps with flash-style running (m, l, acc) scratch that
is (re)initialized at ``n == 0`` and normalized at ``n == N - 1``.  A
page tile is attended against one *leaf tile* at a time, so the fp32
scratch is per-tile instead of spanning the whole batch, and
``max_batch`` can grow without growing VMEM residency (pages are
re-streamed once per leaf tile; tile counts are small, and the default
tile keeps the single-tile IO profile for every batch the serving
engine currently runs).

Layout (what lets Mosaic compile the body: every block is 2-D, and no
reshape or transpose happens inside the kernel).  The wrapper lays the
tile's R = block_b * H query rows on lanes (q enters as (hd, B*H)), and
a page enters as its (S*K, hd) slab, slot-major with the kv head minor.
One matmul scores every query row against every slot of every kv head;
the mask keeps the columns of the row's own kv head (GQA) among the
page's valid slots, for rows whose leaf descends from the page.  The
per-page leaf mask arrives as ``lane_kv`` (N, B*H) int32 — the kv head
of each query row, or -1 — in (8, R) blocks, eight pages per block, so
the block obeys the (8, 128) tiling rule.  Multi-tile grids therefore
need R to be a multiple of 128 on the chip; a single tile may have any
size.

Padding contract (shared with ``build_tree_metadata`` below): the page
axis N is padded to a power of two with *dump entries* — any in-range
page id, ``page_lens == 0``, ``page_mask`` column all zero — and the
batch axis B may contain inactive rows whose mask column is all zero.
Both are inert: a zero-length page contributes no probability mass, and
a fully-masked row produces an all-zero output (no NaNs).  The wrapper
itself pads B up to a multiple of the leaf tile with such inactive rows
and slices them off the output, so callers never see the tile size.

VMEM budget (per-tile): scratch is (hd + 16) * R fp32 (m and l are
(1, R) rows padded to eight sublanes) — e.g. block_b=64, H=32, hd=64
-> 640 KiB — plus the double-buffered (hd, R) q and output blocks and
two (S*K, hd) page slabs, independent of B.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _next_pow2(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


@dataclass(frozen=True)
class TreeMetadata:
    """Host-side tree-attention operands + the IO accounting they imply.

    ``n_unique`` pages are streamed once per step by the tree kernel;
    ``n_logical`` (sum of per-row table lengths) is what per-sequence
    paged attention streams.  ``n_logical / n_unique`` is the measured
    sharing ratio the engine reports.
    """
    page_list: np.ndarray          # (N,) int32, padded with pad_page
    page_mask: np.ndarray          # (N, B) int8, padded entries all-zero
    page_lens: np.ndarray          # (N,) int32, padded entries zero
    n_unique: int                  # live unique pages (pre-padding)
    n_logical: int                 # sum of per-row block-table lengths


def build_tree_metadata(block_tables: Sequence[Sequence[int]],
                        lengths: Sequence[int],
                        page_size: int,
                        *,
                        pad_page: int = 0,
                        min_pages: int = 8,
                        n_rows: Optional[int] = None,
                        check: bool = False) -> TreeMetadata:
    """Derive tree-attention metadata from per-row block tables.

    block_tables[j] lists row j's page ids in path order (empty for an
    inactive/padded row); lengths[j] is its valid token count.  The page
    axis is padded to a power of two (>= min_pages) so jit signatures
    stay O(log max pages); padded entries point at ``pad_page`` with
    zero length and an all-zero mask column.

    With ``check=True`` the tree invariants are asserted: a physical
    page occupies the same table position (hence the same valid length)
    in every row that references it, and every (row, position) pair is
    covered by exactly one unique-page entry.
    """
    B = len(block_tables) if n_rows is None else n_rows
    assert len(block_tables) <= B and len(block_tables) == len(lengths)
    order: dict = {}               # page id -> index into the unique list
    lens: List[int] = []
    n_logical = 0
    for table, ln in zip(block_tables, lengths):
        n_logical += len(table)
        for p, pg in enumerate(table):
            valid = min(page_size, ln - p * page_size)
            assert valid > 0, (pg, p, ln, "table longer than length")
            idx = order.get(pg)
            if idx is None:
                order[pg] = len(lens)
                lens.append(valid)
            elif check:
                assert lens[idx] == valid, \
                    (pg, lens[idx], valid, "shared page, divergent fill")
    n_unique = len(order)
    N = _next_pow2(max(n_unique, 1), min_pages)
    page_list = np.full(N, pad_page, np.int32)
    page_lens = np.zeros(N, np.int32)
    page_mask = np.zeros((N, B), np.int8)
    for pg, idx in order.items():
        page_list[idx] = pg
        page_lens[idx] = lens[idx]
    for j, table in enumerate(block_tables):
        for pg in table:
            page_mask[order[pg], j] = 1
    if check:
        cover = page_mask[:n_unique].sum(axis=0)
        for j, table in enumerate(block_tables):
            assert cover[j] == len(table), (j, cover[j], len(table))
    return TreeMetadata(page_list, page_mask, page_lens,
                        n_unique, n_logical)


def _kernel(page_list_ref, page_lens_ref,       # scalar prefetch
            qt_ref, k_ref, v_ref, lane_kv_ref,  # VMEM
            ot_ref,
            m_ref, l_ref, acc_ref,
            *, scale: float, n_kv_heads: int):
    # grid (B // block_b, N): the page axis trails, so the flash
    # (m, l, acc) carry below sweeps all pages for one leaf tile before
    # the tile advances (scratch re-inits at n == 0 per tile).
    #
    # Layout: lanes are the tile's query rows r = leaf * H + head, so
    # every operand is 2-D and no head regrouping happens in the body.
    # One page is the (S*K, hd) slab of its slots x kv heads; a row only
    # attends to the columns of its own kv head (GQA), which the mask
    # below enforces — columns c = slot * K + kv_head.
    n = pl.program_id(1)
    N = pl.num_programs(1)

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qt = qt_ref[...].astype(jnp.float32)                  # (hd, R)
    k = k_ref[...].astype(jnp.float32)                    # (C, hd)
    v = v_ref[...].astype(jnp.float32)
    # this page's row of the (8, R) lane-kv block: kv head of each query
    # row whose leaf descends from the page, -1 elsewhere
    sub = jax.lax.broadcasted_iota(jnp.int32, lane_kv_ref.shape, 0)
    lane_kv = jnp.max(jnp.where(sub == n % 8, lane_kv_ref[...], -1),
                      axis=0, keepdims=True)              # (1, R)
    C = k.shape[0]
    R = qt.shape[1]
    s = jax.lax.dot_general(
        k, qt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (C, R)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, R), 0)
    ok = (col < page_lens_ref[n] * n_kv_heads) \
        & (col % n_kv_heads == lane_kv)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                   # (1, R)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
    pv = jax.lax.dot_general(
        v, p, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (hd, R)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(n == N - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        ot_ref[...] = (acc_ref[...] / l).astype(ot_ref.dtype)


# Default leaf tile: one tile up to this batch size (the IO profile of
# the old single-level grid), multiple fixed-size tiles beyond it so the
# per-tile scratch stays within the VMEM budget however large max_batch
# grows.
DEFAULT_BLOCK_B = 64


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "block_b"))
def tree_attention(q, k_pool, v_pool, page_list, page_mask, page_lens, *,
                   scale: float, interpret: bool = True,
                   block_b: Optional[int] = None):
    B, H, hd = q.shape
    P, S, K, _ = k_pool.shape
    N = page_list.shape[0]
    G = H // K

    if block_b is None:
        block_b = min(DEFAULT_BLOCK_B, _next_pow2(B, 1))
    block_b = max(1, min(int(block_b), _next_pow2(B, 1)))
    # pad B to a tile multiple with inactive rows (all-zero mask column
    # -> all-zero output, per the padding contract), sliced off below
    Bp = -(-B // block_b) * block_b
    if Bp != B:
        q = jnp.pad(q, ((0, Bp - B), (0, 0), (0, 0)))
        page_mask = jnp.pad(page_mask, ((0, 0), (0, Bp - B)))
    R = block_b * H
    # lane-major operands: query rows (leaf * H + head) on lanes
    qt = q.reshape(Bp * H, hd).T                          # (hd, Bp*H)
    head_kv = jnp.arange(H, dtype=jnp.int32) // G
    lane_kv = jnp.where(page_mask[:, :, None] > 0, head_kv[None, None, :],
                        -1).reshape(N, Bp * H).astype(jnp.int32)
    if N % 8:
        lane_kv = jnp.pad(lane_kv, ((0, 8 - N % 8), (0, 0)),
                          constant_values=-1)
    # a page is its (S*K, hd) slab: slot-major, kv head minor
    kp = k_pool.reshape(P, S * K, hd)
    vp = v_pool.reshape(P, S * K, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bp // block_b, N),
        in_specs=[
            pl.BlockSpec((hd, R), lambda b, n, pls, pln: (0, b)),
            pl.BlockSpec((None, S * K, hd),
                         lambda b, n, pls, pln: (pls[n], 0, 0)),
            pl.BlockSpec((None, S * K, hd),
                         lambda b, n, pls, pln: (pls[n], 0, 0)),
            # eight pages' rows per block (the (8, 128) tiling); the
            # kernel picks row n % 8
            pl.BlockSpec((8, R), lambda b, n, pls, pln: (n // 8, b)),
        ],
        out_specs=pl.BlockSpec((hd, R), lambda b, n, pls, pln: (0, b)),
        scratch_shapes=[
            pltpu.VMEM((1, R), jnp.float32),
            pltpu.VMEM((1, R), jnp.float32),
            pltpu.VMEM((hd, R), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, n_kv_heads=K)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((hd, Bp * H), q.dtype),
        interpret=interpret,
    )(page_list.astype(jnp.int32), page_lens.astype(jnp.int32),
      qt, kp, vp, lane_kv)
    out = out.T.reshape(Bp, H, hd)
    return out[:B] if Bp != B else out
