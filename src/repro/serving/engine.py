"""Paged decode engine: step-synchronous batched decode with tree branching.

The TPU-native stand-in for SGLang's continuous-batching server, scoped to
what PRM tree search actually needs (step-level expand -> score -> prune):

  * a static paged KV pool (repro.kvcache) shared by every live branch;
  * ``prefill_many(prompts)`` — flash-prefill a whole batch of prompts in
    one lock-step stream, writing KV straight into the pool's pages
    (``prefill(tokens)`` is the single-prompt convenience wrapper);
  * ``branch(seq, n)``    — fork block tables (refcount++, CoW last page);
  * ``decode(seq_ids, …)``— ONE jitted step decodes all live branches in
    lock-step against the pool via block tables; implemented on top of
    :class:`DecodeStream`, the persistent slot-based stream whose rows
    can be refilled mid-flight (the online serving loop's token-level
    refill) while preserving per-row bit-identity;
  * free / stats          — physical vs logical page accounting (the
    engine-level measurement behind Table 1's KV reduction);
  * ``swap_out(seq_ids)`` / ``swap_in(seq_ids)`` — page demotion under
    memory pressure: one problem's unique pages are gathered to a
    host-side spill buffer and released (immediately reusable by other
    problems), then later restored onto fresh physical pages as exact
    copies — decode streams resume bit-identically because every
    consumer reads the pool through block tables, never raw page ids.
    The gather is *overlapped*: swap-out snapshots the pages into fresh
    device arrays (async dispatch) and defers the blocking host copy
    until the transfer double-buffer (depth 2) forces the oldest one to
    land or swap-in needs the bytes — demotion traffic hides behind the
    in-flight decode step.  ``swap_out(..., partial=True)`` demotes a
    page-exclusive *subset* of a namespace (a subtree's leaves) instead
    of the whole problem: shared-prefix pages stay hot in the pool and
    only the subtree's exclusive pages travel.  The
    ``swapped_out_pages`` / ``swapped_in_pages`` counters reconcile
    against the allocator's per-ns swap accounting.

Pending-token invariant (the contract between prefill, branch and
decode): after ``prefill(tokens)`` the pool holds KV for
``tokens[:-1]`` and the *last* token is pending — the next decode step
computes its KV (at its reserved slot) together with the next-token
logits.  Every token's KV is therefore written exactly once, by
whichever jitted step consumes it as input, and branching at any point
forks a consistent cache.

Prefill path (``EngineConfig.prefill``):

  * ``"flash"`` (default) — online-softmax flash attention per layer
    (the ``kernels/flash_prefill`` Pallas kernel when ``use_kernel``,
    its pure-jnp blocked formulation otherwise), with each layer's K/V
    scattered *directly* into the pool's pages — no intermediate dense
    cache + copy.  Prompts are right-padded into power-of-two
    (rows, tokens) buckets, so a whole serving run compiles
    O(log max_batch * log max_seq_len) prefill signatures
    (``prefill_traces`` counts them; tests assert the bound).  Padded
    token slots carry position -1 and write to the dump page, so they
    never contaminate real pages and — prompts being right-padded under
    causal masking — never leak into real attention scores.
  * ``"dense"``  — the legacy per-layer ``attn_prefill``-style dense
    attention, kept as the equivalence oracle: both paths agree to fp32
    tolerance on logits and produce bit-identical sampled streams over
    full searches in practice (asserted in tests/test_prefill.py).

Bucket/recompile discipline (shared with the decode and PRM paths): any
host-built operand axis that varies across calls is padded to a power
of two (``pow2_bucket``) before it reaches a jitted step, so the jit
signature count over a run is logarithmic in the largest size seen, not
linear in the number of distinct sizes.  The decode step instead pads
the live set to the static ``max_batch``, so its signature is constant.

Two attention modes for decode (``EngineConfig.attention``):

  * ``"paged"`` — per-sequence paged attention over block tables; a page
    shared by k descendant leaves is streamed k times per step.
  * ``"tree"``  — tree attention over the step's unique live pages
    (DeFT-style): each shared prefix page is streamed once for *all*
    descendant leaves, masked by a per-page descendant bitmap.  The page
    axis is padded to a power of two, so the jitted step compiles
    O(log n_pages) signatures across a whole search run.

Both modes share RoPE positions, KV writes and sampling, and agree to
fp32 tolerance on logits (bit-identical sampled streams in practice).
The engine counts ``unique_pages_streamed`` vs ``logical_pages_streamed``
per decode step — the measured IO sharing ratio that the paper's
Table 2 throughput claims rest on — and attributes both to each
sequence's problem namespace (``*_by_ns``), so a cross-problem sweep
sharing one decode stream still reports per-problem IO.

Sampling is row-keyed (``sample_tokens_rowwise``): each sequence
advances its own PRNG key chain, so its token stream depends only on
its own key and logits — never on batch composition, row order, or
chunk boundaries.  Together with per-row attention independence this
makes decode *composition-independent*: merging many problems'
branches into one stream (the sweep scheduler) reproduces each
problem's solo stream bit-for-bit.

Within a mode, attention runs the pure-jnp reference everywhere, or the
Pallas kernel (interpret on CPU, Mosaic on TPU) when ``use_kernel=True``.

Model families (serving/runtimes.py): the jitted steps do not assume
every layer is KV attention — they thread the residual stream through a
stack of per-layer-group runtimes built from ``cfg.layer_plan()``.
Dense/VLM GQA layers run the historical engine body verbatim
(:class:`AttentionRuntime` — bit-identical to the pre-refactor engine),
MoE layers ride the same attention with a sort-dispatch FFN
(:class:`MoERuntime`), and mamba2/rwkv6 layers keep their constant-size
recurrent state in a :class:`StatePool` — one state page per sequence,
copied on branch, demoted/promoted with the KV spill machinery — so ETS
tree search (branch/prune/swap/demote) works unchanged over pure-SSM
and hybrid (Zamba2) models.  Attention-free models keep a zero-layer KV
pool: block tables still drive token/position bookkeeping, the pool
arrays just hold no bytes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache import KVPool, PageAllocator, StatePool
from repro.kvcache.allocator import OutOfPages
from repro.kvcache.pool import (PendingGather, PendingStateGather,
                                paged_attention_ref)
# the canonical bucketing primitive lives with the pool (kvcache may
# not import serving); re-exported here for the engine-side callers
from repro.kvcache.pool import pow2_bucket  # noqa: F401  (re-export)
from repro.kernels.ref import tree_attention_ref
from repro.obs import span, spanned
from .runtimes import (DecodeCtx, PrefillCtx, build_runtimes,
                       collect_state_specs, total_kv_layers)


@jax.jit
def _advance_keys(keys):
    """Advance every row's key chain one link, in one program.

    Returns ``(next_keys, subkeys)``: per row the two halves of
    ``jax.random.split(k, 2)``, bit for bit.  Rows are independent, so
    a chain's position is the number of iterations its row was live."""
    pair = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return pair[:, 0], pair[:, 1]


@dataclass
class EngineConfig:
    n_pages: int = 512
    page_size: int = 16
    max_batch: int = 64
    max_seq_len: int = 512
    use_kernel: bool = False       # True: Pallas kernels
    attention: str = "paged"       # "paged" | "tree" (see module doc)
    prefill: str = "flash"         # "flash" | "dense" (dense = oracle)
    trace_logits: bool = False     # keep per-step logits (tests only)
    # leaf/query tile for the Pallas decode kernels' two-level grids
    # (None = kernel default); lets max_batch grow past the single-tile
    # VMEM budget — see kernels/tree_attention.py
    kernel_block_b: Optional[int] = None
    # prompts longer than this many tokens prefill in page-streamed
    # segments instead of one bucket (None = always one bucket)
    prefill_chunk_tokens: Optional[int] = None
    # recurrent-state pages (mamba2/rwkv6/hybrid families): one page per
    # live sequence, last page is the dump target.  None = n_pages.
    n_state_pages: Optional[int] = None
    # device mesh for the serve layout (launch.mesh.make_host_mesh /
    # make_production_mesh): the KV pool's page axis shards over
    # "model" (launch.sharding.pool_spec) and per-row decode/prefill
    # operands shard batch -> "data" (engine_batch_spec), while block
    # tables, tree metadata and the allocator stay host/replicated.
    # None (default) keeps the historical single-device engine
    # bit-for-bit; a 1-device mesh is the equivalence oracle — same
    # math, trivially partitioned, identical sampled streams.
    mesh: Optional[object] = None

    def __post_init__(self):
        if self.attention not in ("paged", "tree"):
            raise ValueError(
                f"EngineConfig.attention must be 'paged' or 'tree', got "
                f"{self.attention!r}")
        if self.prefill not in ("flash", "dense"):
            raise ValueError(
                f"EngineConfig.prefill must be 'flash' or 'dense', got "
                f"{self.prefill!r}")
        if self.kernel_block_b is not None and self.kernel_block_b < 1:
            raise ValueError(
                f"EngineConfig.kernel_block_b must be >= 1, got "
                f"{self.kernel_block_b} — pass None for the kernel default")
        if self.prefill_chunk_tokens is not None:
            if self.prefill == "dense":
                raise ValueError(
                    "prefill='dense' is the one-shot equivalence oracle and "
                    "cannot stream long prompts in segments — drop "
                    "prefill_chunk_tokens or use prefill='flash'")
            if self.prefill_chunk_tokens < self.page_size:
                raise ValueError(
                    f"prefill_chunk_tokens={self.prefill_chunk_tokens} is "
                    f"smaller than page_size={self.page_size}: a streamed "
                    f"segment must cover at least one pool page")
        if self.n_state_pages is not None and self.n_state_pages < 2:
            raise ValueError(
                f"n_state_pages={self.n_state_pages} must be >= 2 (one live "
                f"page plus the dump page)")


class PagedEngine:
    def __init__(self, model, params, ecfg: EngineConfig):
        cfg = model.cfg
        if not cfg.supports_decode:
            raise ValueError(
                f"{cfg.name} ({cfg.arch_type}) has no decode path — the "
                f"paged engine serves autoregressive models only")
        if ecfg.attention == "tree" and cfg.is_attention_free:
            raise ValueError(
                f"attention='tree' dedups shared KV pages, but {cfg.name} "
                f"is attention-free (recurrent-only) — use "
                f"attention='paged'")
        if cfg.sliding_window and ecfg.max_seq_len > cfg.sliding_window:
            raise ValueError(
                f"max_seq_len={ecfg.max_seq_len} exceeds {cfg.name}'s "
                f"sliding_window={cfg.sliding_window}: the paged decode "
                f"path keeps every page live and applies no window "
                f"masking, so windowed models must fit inside the window")
        self.model = model
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        # last physical page is the dump target for padded batch rows
        self.dump_page = ecfg.n_pages - 1
        self.alloc = PageAllocator(ecfg.n_pages - 1, ecfg.page_size)
        # model-family runtime stack (serving/runtimes.py): one runtime
        # per layer_plan() group; the KV pool's layer axis covers only
        # the attention-bearing groups (0 layers for pure-SSM models)
        self.runtimes = build_runtimes(model, ecfg)
        L = total_kv_layers(self.runtimes)
        self.n_kv_layers = L
        # mesh-aware layout (EngineConfig.mesh): the pool places its
        # K/V on the serve-policy sharding and per-row host operands
        # are committed batch->data before each jitted step; every
        # divisibility fallback the policy takes lands in
        # ``shard_fallbacks`` so callers can see what replicated.
        # mesh=None skips all of it — the historical engine, and the
        # bit-identity baseline a 1-device mesh is tested against.
        self.mesh = ecfg.mesh
        self.shard_fallbacks: list = []
        self._row_shd_cache: Dict[tuple, object] = {}
        # attention-free models keep a zero-layer pool: the page axes
        # stay (block tables drive token bookkeeping) but the arrays
        # hold no bytes.  Head dims are clamped to 1 so the shape stays
        # well-formed when cfg has no attention heads.
        kvh = max(cfg.n_kv_heads, 1)
        khd = max(cfg.head_dim, 1)
        kv_sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.kernels.ops import check_mesh_compat
            from repro.launch.sharding import pool_spec
            check_mesh_compat(self.mesh, use_kernel=ecfg.use_kernel)
            # commit the weights to this engine's devices once, so each
            # step runs where its pool lives (a replica on its own chip)
            # and never re-transfers them
            self.params = jax.device_put(
                params, NamedSharding(self.mesh, PartitionSpec()))
            pool_shape = (L, ecfg.n_pages, ecfg.page_size, kvh, khd)
            kv_sharding = NamedSharding(
                self.mesh, pool_spec(self.mesh, pool_shape,
                                     record=self.shard_fallbacks))
        self.pool = KVPool(L, ecfg.n_pages, ecfg.page_size,
                           kvh, khd, dtype=jnp.float32,
                           sharding=kv_sharding)
        # recurrent-state pool (None for attention-only stacks): one
        # page per live sequence + the trailing dump page
        state_specs = collect_state_specs(self.runtimes)
        self.state: Optional[StatePool] = None
        self.state_of: Dict[int, int] = {}    # seq_id -> state page
        if state_specs:
            nsp = ecfg.n_state_pages or ecfg.n_pages
            self.state = StatePool(state_specs, nsp)
        self.tokens: Dict[int, List[int]] = {}   # full token history
        self.max_pages_per_seq = -(-ecfg.max_seq_len // ecfg.page_size)
        # throughput accounting (benchmarks/table2): how many decode
        # streams were opened, how many jitted lock-step iterations ran,
        # and how many tokens they produced
        self.n_decode_calls = 0
        self.n_decode_steps = 0
        self.n_decoded_tokens = 0
        # prefill accounting: jitted prefill streams launched and prompt
        # tokens ingested by them (benchmarks/table2 prefill tok/s)
        self.n_prefill_calls = 0
        self.n_prefill_tokens = 0
        # Python-level host->device transfers of step operands
        # (``_put_rows`` / ``_put_repl``): 0 without a mesh, where the
        # jitted step's dispatch commits the host arrays itself
        self.n_host_puts = 0
        # swap accounting (page demotion under memory pressure): pages
        # moved device->host (swap-out) and host->device (swap-in), and
        # the demotion calls that moved them.  Reconciles with the
        # allocator's per-ns ``swapped`` accounting: pages out minus
        # pages dropped while parked minus pages in == pages still in
        # the spill buffer.
        self.swapped_out_pages = 0
        self.swapped_in_pages = 0
        self.n_swap_outs = 0
        self.n_swap_ins = 0
        # recurrent-state pages copied on branch, and the most state
        # pages in use at once (0 for attention-only stacks)
        self.state_pages_copied = 0
        self.state_pages_peak = 0
        # ns -> [(stale page ids, PendingGather)]: the spill buffer a
        # demoted problem's pages wait in until swap-in restores them.
        # A namespace holds a *list* of segments because subtree-grained
        # demotion (partial swap_out) may spill it in several waves.
        self._spill: Dict[int, List[Tuple[List[int], PendingGather]]] = {}
        # ns -> [(seq_ids, PendingStateGather)]: the state-page twin of
        # the KV spill buffer (recurrent families; empty otherwise)
        self._state_spill: Dict[
            int, List[Tuple[List[int], PendingStateGather]]] = {}
        # FIFO of not-yet-materialized spill gathers: at most
        # _spill_buffers transfers stay pending (device snapshots taken,
        # host copy deferred) so demotion overlaps decode without
        # pinning unbounded device memory
        self._pending_spills: List[object] = []
        self._spill_buffers = 2
        # per-step attention IO accounting: pages the attention actually
        # streams (unique — tree mode dedups shared prefixes) vs the
        # per-leaf total a paged read pattern costs.  logical/unique is
        # the measured sharing ratio.  The *_by_ns dicts attribute the
        # same counters to each sequence's problem namespace, so a
        # cross-problem sweep sharing one decode stream still reports
        # per-problem IO (namespaces hold disjoint pages, so the per-ns
        # counts sum to the globals).
        self.unique_pages_streamed = 0
        self.logical_pages_streamed = 0
        self.unique_pages_streamed_by_ns: Dict[int, int] = {}
        self.logical_pages_streamed_by_ns: Dict[int, int] = {}
        # trace-time counters: +1 per compiled decode-step / prefill
        # signature (tests assert the tree step stays O(log n_pages) and
        # prefill stays O(log max_batch * log max_seq_len))
        self.decode_traces = 0
        self.prefill_traces = 0
        self.logits_trace: List[np.ndarray] = []   # if ecfg.trace_logits
        self._decode_fn = self._build_decode_fn()
        self._tree_decode_fn = self._build_tree_decode_fn()
        self._prefill_fn = self._build_prefill_fn()
        self._streamed_prefill_fn = self._build_streamed_prefill_fn()

    # ------------------------------------------------------------------
    # Stats (Table 1 / Fig. 2 measurements)
    # ------------------------------------------------------------------
    def kv_stats(self) -> Dict[str, int]:
        return {
            "physical_pages": self.alloc.used_pages,
            "logical_pages": self.alloc.logical_pages,
            "shared_pages": self.alloc.shared_pages(),
            "swapped_pages": self.alloc.swapped_pages,
            # cumulative attention-IO counters (callers diff successive
            # samples for per-step deltas)
            "unique_pages_streamed": self.unique_pages_streamed,
            "logical_pages_streamed": self.logical_pages_streamed,
        }

    # ------------------------------------------------------------------
    # Jitted model steps
    # ------------------------------------------------------------------
    def _build_prefill_fn(self):
        cfg, model = self.cfg, self.model

        def prefill(params, tokens, positions, pages, slots, lengths,
                    srows, pool_k, pool_v, state):
            """One lock-step prefill over a right-padded prompt bucket.

            tokens/pages/slots (B,T); positions (B,T), -1 at padded
            slots; lengths (B,) valid context tokens per row (0 =
            inactive padding row); srows (B,) state page per row (dump
            for stateless rows).  Attention groups write each layer's
            K/V straight into the pool pages before attention runs —
            padded slots target the dump page, and right-padding under
            the causal mask keeps them out of every valid query's score
            set.  Recurrent groups run the masked chunked scan (identity
            steps past ``lengths``) and write the exact post-prompt
            state into the rows' state pages.
            """
            self.prefill_traces += 1       # trace-time side effect
            B, T = tokens.shape
            if cfg.mrope_sections:
                pos = jnp.broadcast_to(positions[None],
                                       (3,) + positions.shape)
            else:
                pos = positions
            x, pos = model.embed_inputs(params, {"tokens": tokens,
                                                 "positions": pos})
            ctx = PrefillCtx(positions=positions, pos=pos, pages=pages,
                             slots=slots, lengths=lengths, state_rows=srows,
                             x0=x)
            for rt in self.runtimes:
                x, pool_k, pool_v, state = rt.prefill_into_pool(
                    params, x, ctx, pool_k, pool_v, state)
            idx = jnp.clip(lengths - 1, 0, T - 1)
            logits = model.logits(params, x[jnp.arange(B), idx])
            logits = jnp.where((lengths > 0)[:, None], logits, 0.0)
            return logits, pool_k, pool_v, state

        return jax.jit(prefill, donate_argnums=(7, 8, 9))

    def _build_streamed_prefill_fn(self):
        cfg, model = self.cfg, self.model

        def streamed(params, tokens, positions, pages, slots, length,
                     hist_table, hist_len, srows, pool_k, pool_v, state):
            """One segment of a page-streamed long-prompt prefill.

            tokens/positions/pages/slots (1,Ts) — the segment, right
            padded (positions -1, pages -> dump page); length valid
            segment tokens; hist_table (1,Tp) the prompt's block table
            (pow2-padded); hist_len tokens already in the pool; srows
            (1,) the prompt's state page.  Attention groups write the
            segment's KV into the pool, then attend causally within the
            segment AND over the history gathered from the pool through
            the block table — absolute-position masking keeps padded
            table slots and not-yet-written page tails out of every
            score set.  Recurrent groups read the running state from
            the pool and write it back, so each segment continues the
            scan exactly where the previous one stopped (a freshly
            allocated page is the zero empty-history state).
            """
            self.prefill_traces += 1       # trace-time side effect
            B, Ts = tokens.shape
            if cfg.mrope_sections:
                pos = jnp.broadcast_to(positions[None],
                                       (3,) + positions.shape)
            else:
                pos = positions
            x, pos = model.embed_inputs(params, {"tokens": tokens,
                                                 "positions": pos})
            ctx = PrefillCtx(positions=positions, pos=pos, pages=pages,
                             slots=slots,
                             lengths=jnp.full((B,), length, jnp.int32),
                             state_rows=srows, hist_table=hist_table,
                             hist_len=hist_len, x0=x)
            for rt in self.runtimes:
                x, pool_k, pool_v, state = rt.prefill_streamed(
                    params, x, ctx, pool_k, pool_v, state)
            idx = jnp.clip(length - 1, 0, Ts - 1)
            logits = model.logits(params, x[:, idx])
            logits = jnp.where(length > 0, logits, 0.0)
            return logits, pool_k, pool_v, state

        return jax.jit(streamed, donate_argnums=(9, 10, 11))

    def _decode_body(self, params, tokens, lengths, pages, slots, active,
                     srows, pool_k, pool_v, state, attend):
        """Shared body of one lock-step decode over the runtime stack.

        tokens (B,) previous tokens; lengths (B,) context length
        (position of the new token); pages/slots (B,) KV write targets;
        srows (B,) state pages.  ``attend(kv_layer, q, pool_k, pool_v)
        -> (B, H, hd)`` is the only thing the two attention modes
        disagree on — per-row RoPE and KV writes are identical, which
        is what makes them interchangeable.
        """
        cdt = jnp.float32
        x = params["embed"].astype(cdt)[tokens][:, None]   # (B,1,d)
        ctx = DecodeCtx(lengths=lengths, pages=pages, slots=slots,
                        state_rows=srows, attend=attend, x0=x)
        for rt in self.runtimes:
            x, pool_k, pool_v, state = rt.decode_step(
                params, x, ctx, pool_k, pool_v, state)
        logits = self.model.logits(params, x[:, 0])
        logits = jnp.where(active[:, None], logits, 0.0)
        return logits, pool_k, pool_v, state

    def _build_decode_fn(self):
        use_kernel = self.ecfg.use_kernel
        block_b = self.ecfg.kernel_block_b
        scale = self.cfg.attn_scale if self.cfg.head_dim else 1.0

        def step(params, tokens, block_tables, lengths, pages, slots,
                 active, srows, pool_k, pool_v, state):
            """Paged lock-step decode: each row attends over its own
            block table, so shared pages are streamed once per leaf."""
            self.decode_traces += 1        # trace-time side effect

            def attend(l, q, pk, pv):
                if use_kernel:
                    from repro.kernels import ops
                    return ops.paged_attention(q, pk[l], pv[l],
                                               block_tables, lengths + 1,
                                               scale=scale,
                                               block_b=block_b)
                return paged_attention_ref(q, pk[l], pv[l], block_tables,
                                           lengths + 1, scale=scale)

            return self._decode_body(params, tokens, lengths, pages, slots,
                                     active, srows, pool_k, pool_v, state,
                                     attend)

        return jax.jit(step, donate_argnums=(8, 9, 10))

    def _build_tree_decode_fn(self):
        use_kernel = self.ecfg.use_kernel
        block_b = self.ecfg.kernel_block_b
        scale = self.cfg.attn_scale if self.cfg.head_dim else 1.0

        def step(params, tokens, lengths, pages, slots, active,
                 page_list, page_mask, page_lens, srows, pool_k, pool_v,
                 state):
            """Tree lock-step decode: attention walks the unique live
            pages of the whole tree (page_list padded to a power of two,
            zero-length entries inert), so a shared prefix page is
            streamed once for all descendant rows."""
            self.decode_traces += 1        # trace-time side effect

            def attend(l, q, pk, pv):
                if use_kernel:
                    from repro.kernels import ops
                    return ops.tree_attention(q, pk[l], pv[l], page_list,
                                              page_mask, page_lens,
                                              scale=scale,
                                              block_b=block_b)
                return tree_attention_ref(q, pk[l], pv[l], page_list,
                                          page_mask, page_lens,
                                          scale=scale)

            return self._decode_body(params, tokens, lengths, pages, slots,
                                     active, srows, pool_k, pool_v, state,
                                     attend)

        return jax.jit(step, donate_argnums=(10, 11, 12))

    # ------------------------------------------------------------------
    # Mesh placement of host-built operands
    # ------------------------------------------------------------------
    def _put_rows(self, arr):
        """Commit a batch-leading host operand (tokens, lengths, write
        pages/slots, active mask — anything whose axis 0 is the row
        grid) with the serve policy's batch->``data`` sharding.  The
        per-shape NamedSharding is cached, so fallback recording fires
        once per shape, not once per step.  Without a mesh the host
        array itself is returned: the jitted step's own dispatch commits
        it to the default device, with no Python-level transfer (same
        bits, same compiled program)."""
        if self.mesh is None:
            return arr
        shape = np.shape(arr)
        shd = self._row_shd_cache.get(shape)
        if shd is None:
            from jax.sharding import NamedSharding
            from repro.launch.sharding import engine_batch_spec
            shd = NamedSharding(
                self.mesh, engine_batch_spec(self.mesh, shape,
                                             record=self.shard_fallbacks))
            self._row_shd_cache[shape] = shd
        self.n_host_puts += 1
        return jax.device_put(np.asarray(arr), shd)

    def _put_repl(self, arr):
        """Commit a host operand replicated across the mesh: block
        tables and the tree step's unique-page metadata (page lists,
        descendant bitmaps, page lengths) index the *whole* pool, so
        every shard needs all of them — the mesh-obliviousness contract
        of the allocator's tree-metadata derivation.  Without a mesh
        the host array itself, as in ``_put_rows``."""
        if self.mesh is None:
            return arr
        shd = self._row_shd_cache.get(("repl",))
        if shd is None:
            from jax.sharding import NamedSharding, PartitionSpec
            shd = NamedSharding(self.mesh, PartitionSpec())
            self._row_shd_cache[("repl",)] = shd
        self.n_host_puts += 1
        return jax.device_put(np.asarray(arr), shd)

    # ------------------------------------------------------------------
    # Public host API
    # ------------------------------------------------------------------
    def prefill(self, tokens: Sequence[int]) -> int:
        """Run one prompt; returns seq_id.  See ``prefill_many``."""
        return self.prefill_many([tokens])[0]

    def prefill_many(self, prompts: Sequence[Sequence[int]],
                     ns: Optional[Sequence[int]] = None) -> List[int]:
        """Ingest a batch of prompts in one lock-step prefill stream.

        Pages for *all* prompts are allocated in a single
        ``PageAllocator.new_seqs`` pass (all-or-nothing, so a mid-batch
        ``OutOfPages`` can't leave stragglers), then the whole batch is
        right-padded into a power-of-two (rows, tokens) bucket and runs
        through the jitted flash-prefill step, which writes each layer's
        KV directly into the pool pages.  Prompt batches larger than
        ``max_batch`` are chunked (the only case with more than one
        prefill stream per call).  Returns seq_ids in prompt order.
        All returned sequences hold their pages until freed, so the
        pool must have room for the whole batch at once (the up-front
        ``new_seqs`` check raises ``OutOfPages`` before anything is
        allocated otherwise).

        Invariant: the pool holds KV for each prompt's ``tokens[:-1]``;
        the last token is *pending* — the next decode step computes its
        KV (at its reserved slot) together with the next-token logits.
        This keeps prefill, branching and decode consistent: every
        token's KV is written exactly once, by whichever step consumes
        it as input.
        """
        all_toks = [[int(t) for t in p] for p in prompts]
        assert all(all_toks), "empty prompt"
        assert all(len(t) <= self.ecfg.max_seq_len for t in all_toks), \
            "prompt exceeds max_seq_len"
        ctxs = [t[:-1] for t in all_toks]
        # all-or-nothing across BOTH pools: check state capacity before
        # the allocator commits KV pages, allocate state pages after
        if self.state is not None and len(ctxs) > self.state.n_free:
            raise OutOfPages(
                f"state pool exhausted: need {len(ctxs)} pages, "
                f"{self.state.n_free} free")
        handles = self.alloc.new_seqs([len(c) for c in ctxs], ns=ns)
        if self.state is not None:
            spages = self.state.alloc(len(handles))   # zeroed at alloc
            for h, pg in zip(handles, spages):
                self.state_of[h.seq_id] = pg
            self._note_state_peak()
        for h, t in zip(handles, all_toks):
            self.tokens[h.seq_id] = t
        pct = self.ecfg.prefill_chunk_tokens
        streamed = {i for i, c in enumerate(ctxs)
                    if pct is not None and len(c) > pct}
        rest = [i for i in range(len(handles)) if i not in streamed]
        mb = self.ecfg.max_batch
        chunks = [([handles[i] for i in rest[j:j + mb]],
                   [ctxs[i] for i in rest[j:j + mb]])
                  for j in range(0, len(rest), mb)]
        # software pipeline: launching chunk k is an async jax dispatch,
        # so the host builds chunk k+1's padded operand arrays while the
        # device is still computing chunk k
        pending = self._prep_prefill_chunk(*chunks[0]) if chunks else None
        for j in range(len(chunks)):
            self._launch_prefill_chunk(pending)
            pending = (self._prep_prefill_chunk(*chunks[j + 1])
                       if j + 1 < len(chunks) else None)
        for i in sorted(streamed):
            self._prefill_streamed(handles[i], ctxs[i])
        return [h.seq_id for h in handles]

    def _prep_prefill_chunk(self, handles, ctxs):
        """Host half of one prefill stream: build the right-padded
        power-of-two operand arrays for <= max_batch prompts (no device
        work — the pipelined ``prefill_many`` loop runs this for chunk
        k+1 while the device executes chunk k)."""
        if not any(ctxs):
            return None            # single-token prompts: nothing to write
        ps = self.ecfg.page_size
        T = pow2_bucket(max(len(c) for c in ctxs))
        Bp = pow2_bucket(len(ctxs), lo=1)
        tok = np.zeros((Bp, T), np.int32)
        pos = np.full((Bp, T), -1, np.int32)
        pages = np.full((Bp, T), self.dump_page, np.int32)
        slots = np.zeros((Bp, T), np.int32)
        lens = np.zeros(Bp, np.int32)
        srows = self._state_rows([h.seq_id for h in handles], Bp)
        n_tokens = 0
        for r, (h, ctx) in enumerate(zip(handles, ctxs)):
            n = len(ctx)
            if not n:
                continue
            tok[r, :n] = ctx
            pos[r, :n] = np.arange(n)
            pages[r, :n] = np.repeat(h.block_table, ps)[:n]
            slots[r, :n] = np.tile(np.arange(ps), len(h.block_table))[:n]
            lens[r] = n
            n_tokens += n
        return tok, pos, pages, slots, lens, srows, n_tokens

    def _state_rows(self, seq_ids, n_rows: int) -> np.ndarray:
        """(n_rows,) state page per row; dump page for padding rows and
        for attention-only stacks (whose jitted steps carry an empty
        state dict — the indices are then inert)."""
        dump = self.state.dump_page if self.state is not None else 0
        srows = np.full(n_rows, dump, np.int32)
        for r, sid in enumerate(seq_ids):
            if sid is not None and sid in self.state_of:
                srows[r] = self.state_of[sid]
        return srows

    def _state_in(self):
        return self.state.arrays if self.state is not None else {}

    def _state_out(self, new) -> None:
        if self.state is not None:
            self.state.arrays = new

    def _launch_prefill_chunk(self, prep) -> None:
        """Device half of one prefill stream: dispatch the jitted step
        over arrays ``_prep_prefill_chunk`` built (async under jax)."""
        if prep is None:
            return
        tok, pos, pages, slots, lens, srows, n_tokens = prep
        self.n_prefill_calls += 1
        self.n_prefill_tokens += n_tokens
        logits, self.pool.k, self.pool.v, new_state = self._prefill_fn(
            self.params, self._put_rows(tok), self._put_rows(pos),
            self._put_rows(pages), self._put_rows(slots),
            self._put_rows(lens), self._put_rows(srows),
            self.pool.k, self.pool.v, self._state_in())
        self._state_out(new_state)
        if self.ecfg.trace_logits:
            self.logits_trace.append(np.asarray(logits))

    def _prefill_chunk(self, handles, ctxs) -> None:
        """One jitted prefill stream over <= max_batch prompts."""
        self._launch_prefill_chunk(self._prep_prefill_chunk(handles, ctxs))

    def _prefill_streamed(self, h, ctx) -> None:
        """Page-streamed prefill of ONE very long prompt.

        The prompt's context runs in sequential token segments of at
        most ``prefill_chunk_tokens``: each segment's KV is written
        into the pool, then its queries attend causally within the
        segment plus over the *history* gathered from the prompt's own
        pool pages through its block table — so peak activation memory
        is one segment, not the whole prompt, and earlier segments'
        KV never leaves the pool.  Segment lengths and the history
        table are power-of-two bucketed, keeping the signature count
        O(log chunk x log pages).  The final segment's last-token
        logits match the one-shot path (same pending-token contract).
        """
        n = len(ctx)
        if not n:
            return
        ps = self.ecfg.page_size
        pct = self.ecfg.prefill_chunk_tokens
        Tp = pow2_bucket(len(h.block_table), lo=1)
        tbl = np.zeros((1, Tp), np.int32)
        tbl[0, :len(h.block_table)] = h.block_table
        tbl_j = self._put_repl(tbl)
        srows = self._state_rows([h.seq_id], 1)
        for s0 in range(0, n, pct):
            s1 = min(s0 + pct, n)
            seg = ctx[s0:s1]
            Ts = pow2_bucket(len(seg), lo=1)
            tok = np.zeros((1, Ts), np.int32)
            pos = np.full((1, Ts), -1, np.int32)
            pages = np.full((1, Ts), self.dump_page, np.int32)
            slots = np.zeros((1, Ts), np.int32)
            m = len(seg)
            tok[0, :m] = seg
            idx = np.arange(s0, s1)
            pos[0, :m] = idx
            pages[0, :m] = [h.block_table[i // ps] for i in idx]
            slots[0, :m] = idx % ps
            self.n_prefill_calls += 1
            self.n_prefill_tokens += m
            logits, self.pool.k, self.pool.v, new_state = \
                self._streamed_prefill_fn(
                    self.params, self._put_rows(tok), self._put_rows(pos),
                    self._put_rows(pages), self._put_rows(slots),
                    np.int32(m), tbl_j,
                    np.int32(s0), self._put_rows(srows),
                    self.pool.k, self.pool.v, self._state_in())
            self._state_out(new_state)
        if self.ecfg.trace_logits:
            self.logits_trace.append(np.asarray(logits))

    def branch(self, seq_id: int, n: int) -> List[int]:
        if self.state is not None and n > self.state.n_free:
            raise OutOfPages(
                f"state pool exhausted: need {n} pages, "
                f"{self.state.n_free} free")
        handles = self.alloc.branch(seq_id, n)
        for b in handles:
            self.tokens[b.seq_id] = list(self.tokens[seq_id])
        if self.state is not None:
            # recurrent state has no prefix sharing: every branch eagerly
            # copies the parent's constant-size page (copy-on-branch)
            with span("engine.state.copy"):
                pages = self.state.alloc(len(handles))
                self.state.copy_page(self.state_of[seq_id], pages)
            for b, pg in zip(handles, pages):
                self.state_of[b.seq_id] = pg
            self.state_pages_copied += len(pages)
            self._note_state_peak()
        return [b.seq_id for b in handles]

    def _note_state_peak(self) -> None:
        used = self.state.dump_page - self.state.n_free
        self.state_pages_peak = max(self.state_pages_peak, used)

    def free(self, seq_id: int) -> None:
        h = self.alloc.seqs.get(seq_id)
        ns = h.ns if h is not None else None
        was_swapped = h.swapped if h is not None else False
        self.alloc.free_seq(seq_id)
        self.tokens.pop(seq_id, None)
        pg = self.state_of.pop(seq_id, None)
        if pg is not None and self.state is not None:
            self.state.release([pg])
        # last swapped sequence of a parked namespace gone -> its spill
        # buffer can never be swapped back in; drop the host copy
        if was_swapped and ns not in self.alloc.swapped:
            self._drop_spill(ns)

    # ------------------------------------------------------------------
    # Swap: page demotion to a host-side spill buffer (memory pressure)
    # ------------------------------------------------------------------
    def swap_out(self, seq_ids: Sequence[int], *,
                 partial: bool = False) -> int:
        """Demote sequences: spill their exclusive pages to host, free
        them.

        Default: ``seq_ids`` is every live sequence of one namespace
        (the sweep scheduler passes the backend's per-problem sequence
        set).  With ``partial=True`` any subset of one namespace works —
        only the subset-exclusive pages travel; shared-prefix pages
        stay hot in the pool (subtree-grained spill).  The pages' K/V
        are snapshotted into fresh device arrays *before* the allocator
        releases them (async dispatch — the blocking host copy is
        deferred until the transfer double-buffer forces it or swap-in
        needs the bytes), so the freed pages are immediately reusable
        by other problems while the copy-out overlaps in-flight decode.
        Returns the number of pages spilled.
        """
        ids = list(seq_ids)
        if not ids:
            return 0
        ns = self.alloc.seqs[ids[0]].ns
        if not partial:
            assert ns not in self._spill, (ns, "already swapped out")
        # snapshot BEFORE releasing: the pool content of a freed page is
        # only guaranteed until the next allocation writes over it
        pages = self.alloc.exclusive_pages(ids)
        gather = self.pool.gather_pages_async(pages)
        released = self.alloc.swap_out_seqs(ids, partial=partial)
        assert released == pages, (released, pages)
        self._spill.setdefault(ns, []).append((pages, gather))
        self._pending_spills.append(gather)
        if self.state is not None:
            # recurrent-state pages are per-sequence exclusive: spill one
            # page per demoted id and free it alongside the KV pages
            spages = [self.state_of.pop(i) for i in ids]
            sgather = self.state.gather_pages_async(spages)
            self.state.release(spages)
            self._state_spill.setdefault(ns, []).append((ids, sgather))
            self._pending_spills.append(sgather)
        while len(self._pending_spills) > self._spill_buffers:
            self._pending_spills.pop(0).resolve()
        self.swapped_out_pages += len(pages)
        self.n_swap_outs += 1
        return len(pages)

    def swap_in(self, seq_ids: Sequence[int]) -> int:
        """Restore a demoted problem's pages from the spill buffer.

        Allocates fresh physical pages (all-or-nothing; raises
        ``OutOfPages`` leaving everything parked when the pool lacks
        room), scatters the spilled K/V copies into them — resolving
        any still-pending transfer first — and rewrites the problem's
        block tables.  Every spill segment of the namespace (a
        subtree-grained demotion may have several) restores in one
        call.  Restored pages are exact copies, so the problem's decode
        streams resume bit-identically — physical ids changed, but
        every consumer indexes the pool through the block tables.
        Returns the number of pages restored.
        """
        ids = list(seq_ids)
        if not ids:
            return 0
        ns = self.alloc.seqs[ids[0]].ns
        segments = self._spill.get(ns, [])
        idset = set(ids)
        if self.state is not None:
            need = sum(sum(1 for sid in seg_ids if sid in idset)
                       for seg_ids, _ in self._state_spill.get(ns, []))
            if need > self.state.n_free:
                # all-or-nothing across both pools: refuse before the KV
                # restore so everything stays parked
                raise OutOfPages(
                    f"state pool exhausted: need {need} pages, "
                    f"{self.state.n_free} free")
        mapping = self.alloc.swap_in_seqs(ids)     # may raise OutOfPages
        restored = 0
        for pages, gather in segments:
            host_k, host_v = gather.resolve()
            # sequences freed while parked may have dropped spill pages
            rows = [i for i, pg in enumerate(pages) if pg in mapping]
            if rows:
                self.pool.scatter_pages(
                    [mapping[pages[i]] for i in rows],
                    host_k[:, rows], host_v[:, rows],
                    dump_page=self.dump_page)
            restored += len(rows)
        if self.state is not None:
            for seg_ids, sgather in self._state_spill.get(ns, []):
                host = sgather.resolve()
                rows = [j for j, sid in enumerate(seg_ids)
                        if sid in idset]
                if rows:
                    npages = self.state.alloc(len(rows))
                    self.state.scatter_pages(
                        npages, {k: a[:, rows] for k, a in host.items()})
                    for pg, j in zip(npages, rows):
                        self.state_of[seg_ids[j]] = pg
            self._note_state_peak()
        self._drop_spill(ns)
        self.swapped_in_pages += restored
        self.n_swap_ins += 1
        return restored

    def _drop_spill(self, ns: Optional[int]) -> None:
        """Forget a namespace's spill segments (restored or orphaned)
        and un-pin their device snapshots from the pending-transfer
        FIFO."""
        for _, gather in self._spill.pop(ns, []):
            if gather in self._pending_spills:
                self._pending_spills.remove(gather)
        for _, gather in self._state_spill.pop(ns, []):
            if gather in self._pending_spills:
                self._pending_spills.remove(gather)

    def reset(self) -> None:
        """Free every live sequence; keeps the pool and compiled steps.

        Lets one engine serve a stream of independent search problems
        without re-jitting prefill/decode (benchmarks, serving loops).
        Cumulative throughput/IO counters are kept (callers zero them
        explicitly when they delimit a measurement window)."""
        for sid in list(self.alloc.seqs):
            self.free(sid)
        self._spill.clear()
        self._state_spill.clear()
        self._pending_spills.clear()
        self.logits_trace.clear()

    def reset_counters(self) -> None:
        """Zero the throughput and attention-IO counters (measurement
        window delimiter for benchmarks and traces)."""
        self.n_decode_calls = 0
        self.n_decode_steps = 0
        self.n_decoded_tokens = 0
        self.n_prefill_calls = 0
        self.n_prefill_tokens = 0
        self.n_host_puts = 0
        self.swapped_out_pages = 0
        self.swapped_in_pages = 0
        self.n_swap_outs = 0
        self.n_swap_ins = 0
        self.state_pages_copied = 0
        self.state_pages_peak = 0
        if self.state is not None:
            self._note_state_peak()
        self.unique_pages_streamed = 0
        self.logical_pages_streamed = 0
        self.unique_pages_streamed_by_ns.clear()
        self.logical_pages_streamed_by_ns.clear()

    # ------------------------------------------------------------------
    def _count_streamed_pages(self, live: Sequence[int],
                              n_unique: int, n_logical: int) -> None:
        """Book one decode iteration's attention IO, globally and per
        problem namespace.  Namespaces hold disjoint pages (branching
        never crosses them), so per-ns unique counts sum to the global
        unique count in tree mode too."""
        self.unique_pages_streamed += n_unique
        self.logical_pages_streamed += n_logical
        handles = [self.alloc.seqs.get(i) for i in live]
        if any(h is None or not hasattr(h, "ns") for h in handles):
            return            # engine doubles: global accounting only
        uniq_ns = self.unique_pages_streamed_by_ns
        log_ns = self.logical_pages_streamed_by_ns
        ns_tags = {h.ns for h in handles}
        if len(ns_tags) == 1:
            # fast path (solo runs, single-problem steps): the global
            # counts ARE this namespace's — skip the per-ns page unions
            ns = handles[0].ns
            uniq_ns[ns] = uniq_ns.get(ns, 0) + n_unique
            log_ns[ns] = log_ns.get(ns, 0) + n_logical
            return
        tree_mode = self.ecfg.attention == "tree"
        pages_by_ns: Dict[int, set] = {}
        for h in handles:
            npg = len(h.block_table)
            log_ns[h.ns] = log_ns.get(h.ns, 0) + npg
            if tree_mode:
                pages_by_ns.setdefault(h.ns, set()).update(h.block_table)
            else:
                # paged reads stream every page of every row
                uniq_ns[h.ns] = uniq_ns.get(h.ns, 0) + npg
        for ns, pages in pages_by_ns.items():
            uniq_ns[ns] = uniq_ns.get(ns, 0) + len(pages)

    def _pad_key_block(self):
        """(max_batch,) inert key chains for unoccupied decode rows.

        Cached: the pad keys never carry sampled values (inactive rows'
        samples are discarded), they only keep the all-rows key split
        shape-static."""
        cache = getattr(self, "_pad_keys", None)
        if cache is None or cache.shape[0] < self.ecfg.max_batch:
            cache = jax.random.split(jax.random.key(0), self.ecfg.max_batch)
            self._pad_keys = cache
        return cache[:self.ecfg.max_batch]

    def open_stream(self, temperature: float = 1.0,
                    stop_tokens: Sequence[int] = ()) -> "DecodeStream":
        """Open a persistent row-refillable decode stream (see
        :class:`DecodeStream`)."""
        return DecodeStream(self, temperature=temperature,
                            stop_tokens=stop_tokens)

    def decode(self, seq_ids: Sequence[int], n_tokens: int,
               key=None, temperature: float = 1.0,
               stop_tokens: Sequence[int] = (),
               row_keys=None) -> Dict[int, List[int]]:
        """Decode up to n_tokens for each sequence, lock-step batched.

        Stops a sequence early when a stop token is emitted (the stop
        token is included in the returned step).  Returns new tokens per
        seq_id.

        Sampling is row-keyed: each sequence advances its own PRNG key
        chain (one split per lock-step iteration it is live for) and
        samples with :func:`sample_tokens_rowwise`, so its token stream
        depends only on its own key, logits and stop history — never on
        which other sequences share the batch, their order, or where
        chunk boundaries fall.  Callers pass either ``row_keys`` (one
        key per sequence — the sweep scheduler derives them per problem
        so cross-problem batches reproduce solo runs bit-for-bit) or a
        single ``key`` that is split into per-row chains.

        Implemented as the drain-to-empty special case of
        :class:`DecodeStream`: all sequences enter together and the
        stream runs until the last one stops — exactly the historical
        closed loop, so every caller of ``decode`` keeps its streams
        bit-for-bit while the serving loop refills the same stream
        mid-flight.
        """
        ecfg = self.ecfg
        ids = list(seq_ids)
        assert len(ids) <= ecfg.max_batch, (len(ids), ecfg.max_batch)
        if row_keys is None:
            assert key is not None, "pass key or row_keys"
            row_keys = jax.random.split(key, len(ids))
        self.n_decode_calls += 1
        if n_tokens <= 0:
            return {i: [] for i in ids}
        stream = DecodeStream(self, temperature=temperature,
                              stop_tokens=stop_tokens)
        stream.add(ids, row_keys, n_tokens)
        while stream.live:
            stream.step()
        return {i: stream.out[i] for i in ids}


class DecodeStream:
    """Persistent row-refillable lock-step decode over one engine.

    Generalizes the engine's ``decode()`` loop: sequences occupy slots
    of the static ``max_batch`` row grid, ``step()`` runs ONE jitted
    lock-step iteration over the occupied slots, and ``add()`` may seat
    new sequences into free slots at ANY iteration boundary — including
    while other rows keep decoding.  This is the token-level refill the
    online serving loop is built on: when a row stops mid-step (stop
    token / budget), its slot backfills from another live problem's
    demand instead of waiting for a global step barrier.

    Bit-identity contract: a row's sampled stream depends only on its
    own key chain (seeded by its ``add()`` row key, advanced once per
    iteration it occupies a slot), its own logits (per-row attention
    over its own pages) and its stop history — never on which slots are
    occupied around it, when it was added, or when neighbours retire.
    Any add/retire schedule therefore reproduces the one-call
    ``decode()`` streams bit-for-bit; ``decode()`` itself is the
    add-everything-then-drain special case.
    """

    def __init__(self, engine: PagedEngine, *, temperature: float = 1.0,
                 stop_tokens: Sequence[int] = ()):
        self.engine = engine
        self.temperature = temperature
        self.stop = set(int(s) for s in stop_tokens)
        B = engine.ecfg.max_batch
        self._slot_seq: List[Optional[int]] = [None] * B
        self._slot_of: Dict[int, int] = {}
        self._budget: Dict[int, int] = {}
        # every slot always carries a key chain; free slots hold inert
        # pad chains whose samples are never consumed
        self._keys = engine._pad_key_block()
        self.out: Dict[int, List[int]] = {}

    @property
    def live(self) -> List[int]:
        """Sequences currently decoding, in slot order."""
        return [i for i in self._slot_seq if i is not None]

    @property
    def n_free(self) -> int:
        return sum(1 for s in self._slot_seq if s is None)

    def add(self, seq_ids: Sequence[int], row_keys, n_tokens: int) -> None:
        """Seat sequences into free slots (lowest index first), each with
        its own sampling key and a per-row budget of ``n_tokens``."""
        ids = list(seq_ids)
        if not ids:
            return
        keys = jnp.asarray(row_keys)
        assert keys.shape[0] == len(ids), (keys.shape, len(ids))
        free = [j for j, s in enumerate(self._slot_seq) if s is None]
        assert len(ids) <= len(free), (len(ids), len(free))
        taken = free[:len(ids)]
        for j, i in zip(taken, ids):
            assert i not in self._slot_of, (i, "already streaming")
            self._slot_seq[j] = i
            self._slot_of[i] = j
            self._budget[i] = int(n_tokens)
            self.out[i] = []
        self._keys = self._keys.at[jnp.asarray(taken)].set(keys)

    def _free_slot(self, i: int) -> None:
        # the retired slot's key chain stays in the array and keeps
        # advancing inertly until add() overwrites it with a fresh key
        j = self._slot_of.pop(i)
        self._slot_seq[j] = None
        self._budget.pop(i, None)

    @spanned("engine.decode")
    def step(self) -> List[int]:
        """Run ONE lock-step iteration over the occupied slots.

        Returns the sequences that stopped this iteration (stop token,
        per-row budget, or max_seq_len) — their slots are free for
        ``add()`` before the next iteration.
        """
        from .sampler import sample_tokens_rowwise
        eng = self.engine
        ecfg = eng.ecfg
        tree_mode = ecfg.attention == "tree"
        live = self.live
        if not live:
            return []
        eng.n_decode_steps += 1
        with span("engine.decode.reserve"):
            # reserve one slot per live sequence (may CoW)
            copy_ops = []
            for i in live:
                copy_ops += eng.alloc.append_tokens(i, 1)
            eng.pool.copy_pages(copy_ops)

        with span("engine.decode.metadata"):
            B = ecfg.max_batch
            T = eng.max_pages_per_seq
            tok = np.zeros(B, np.int32)
            bt = None if tree_mode else np.full((B, T), -1, np.int32)
            lens = np.zeros(B, np.int32)
            pages = np.full(B, eng.dump_page, np.int32)   # inactive -> dump
            slots = np.zeros(B, np.int32)
            act = np.zeros(B, bool)
            rows: List[Optional[int]] = [None] * B
            for j, i in enumerate(self._slot_seq):
                if i is None:
                    continue
                h = eng.alloc.seqs[i]
                tok[j] = eng.tokens[i][-1]
                if not tree_mode:
                    bt[j, :len(h.block_table)] = h.block_table
                pos = h.length - 1          # slot reserved for the new token
                lens[j] = pos
                pages[j] = h.block_table[pos // ecfg.page_size]
                slots[j] = pos % ecfg.page_size
                act[j] = True
                rows[j] = i

            srows = eng._state_rows(rows, B)
            if tree_mode:
                meta = eng.alloc.tree_metadata(rows, pad_page=eng.dump_page)
                eng._count_streamed_pages(live, meta.n_unique,
                                          meta.n_logical)
            else:
                # paged reads stream every page of every live row
                n_logical = sum(len(eng.alloc.seqs[i].block_table)
                                for i in live)
                eng._count_streamed_pages(live, n_logical, n_logical)

        with span("engine.decode.launch"):
            if tree_mode:
                # rows shard batch->data; the unique-page metadata spans
                # the whole tree (no batch axis) and stays replicated
                logits, eng.pool.k, eng.pool.v, new_state = \
                    eng._tree_decode_fn(
                        eng.params, eng._put_rows(tok), eng._put_rows(lens),
                        eng._put_rows(pages), eng._put_rows(slots),
                        eng._put_rows(act), eng._put_repl(meta.page_list),
                        eng._put_repl(meta.page_mask),
                        eng._put_repl(meta.page_lens), eng._put_rows(srows),
                        eng.pool.k, eng.pool.v, eng._state_in())
            else:
                logits, eng.pool.k, eng.pool.v, new_state = eng._decode_fn(
                    eng.params, eng._put_rows(tok), eng._put_repl(bt),
                    eng._put_rows(lens), eng._put_rows(pages),
                    eng._put_rows(slots), eng._put_rows(act),
                    eng._put_rows(srows), eng.pool.k, eng.pool.v,
                    eng._state_in())
            eng._state_out(new_state)
            if ecfg.trace_logits:
                eng.logits_trace.append(np.asarray(logits))
            # advance every slot's own key chain (freed slots' keys
            # advance too, but their samples are never consumed — a
            # row's stream depends only on how many iterations it was
            # live for)
            self._keys, subs = _advance_keys(self._keys)
            sampled = sample_tokens_rowwise(subs, logits, self.temperature)
        with span("engine.decode.wait"):
            new = np.asarray(sampled)
        with span("engine.decode.commit"):
            finished: List[int] = []
            for j, i in enumerate(self._slot_seq):
                if i is None:
                    continue
                t = int(new[j])
                eng.tokens[i].append(t)
                self.out[i].append(t)
                eng.n_decoded_tokens += 1
                self._budget[i] -= 1
                if t in self.stop \
                        or len(eng.tokens[i]) >= ecfg.max_seq_len \
                        or self._budget[i] <= 0:
                    finished.append(i)
            for i in finished:
                self._free_slot(i)
        return finished
