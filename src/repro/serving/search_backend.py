"""LM search backend: the real end-to-end driver behind the controllers.

Wires the paged engine (search LM), a PRM (LM with value head) and a small
encoder embedder into the ``repro.core.controllers.Backend`` protocol:

  expand — branch the leaf's sequence (block-table fork, CoW) and decode
           one reasoning step per branch (until the step delimiter / EOS);
  score  — PRM reward at the trajectory's last position (paper §5.1 uses
           the final PRM score of each step);
  embed  — mean-pooled encoder state of the *last step's* tokens (§4.2);
  answer — task-specific extractor over the finished trajectory.

Batched step protocol (the serving idiom the paper's throughput numbers
depend on — one search step costs one decode stream and O(1) jit
signatures):

  start_many  — prefill every prompt of a multi-problem sweep in one
      batched, length-bucketed flash-prefill stream
      (``engine.prefill_many``); pending roots are protected from
      ``on_step``'s free-sweep until their own search branches them.
  expand_many — branch *all* live leaves up front, then decode every new
      branch in a single lock-step batched ``engine.decode`` call;
      when the total branch count exceeds ``engine.ecfg.max_batch`` the
      branch list is split into ``max_batch`` chunks (the only case with
      more than one decode stream per step).
  score_many  — one PRM forward over all candidates.  Sequences are
      right-padded into power-of-two length buckets (and the batch into a
      power-of-two row count), with padded positions set to -1 so the
      attention mask excludes them; the jitted scorer therefore compiles
      once per (batch-bucket, length-bucket) pair instead of once per
      distinct sequence length.  The per-row reward is gathered at each
      sequence's true last position.
  embed_many  — same bucketing for the (bidirectional) encoder; the
      position mask keeps padding out of the attention, and the mean
      pool runs over valid positions only, so batched embeddings match
      the single-node path.

Cross-problem sweep protocol (``expand_multi`` / ``score_multi`` /
``embed_multi``, driven by ``repro.core.controllers.SweepScheduler``):
each takes ``[(tree, request), ...]`` for many problems and batches the
union into the SAME single stream the ``*_many`` path uses — one decode
over every problem's branches, one padded PRM/embedder call over every
problem's candidates.  The single-problem ``*_many`` methods are the
one-request special case of the multi path, so both share RNG and shape
discipline.

Problem namespaces replace ``reset()``-based isolation: every problem a
sweep admits keeps its own

  * engine sequence namespace (``SequenceHandle.ns``; pages and IO are
    attributed per problem by the allocator/engine),
  * sampling-key chain, seeded exactly like a fresh ``reset()`` would —
    and consumed one step-key per expand call, with per-branch row keys
    (``fold_in(step_key, branch_index)``) fed to the engine's row-keyed
    sampler.  A branch's token stream therefore depends only on its own
    problem's RNG and its own logits, never on which other problems
    share the decode batch or where chunk boundaries fall — which is
    why a cross-problem sweep is bit-identical to running each problem
    solo on a freshly reset backend,
  * KV/IO trace (``kv_trace_by_problem``; ``io_summary(ns=...)``
    reduces one problem's trace — what ``SearchResult.kv_summary``
    reports in a sweep).

Memory-pressure protocol (``capacity`` / ``prompt_pages`` /
``step_pages_per_branch`` / ``problem_pages`` / ``problem_swapped_pages``
/ ``swap_out_problem`` / ``swap_in_problem``): the sweep scheduler's
admission control reserves a per-problem working-set estimate against
``capacity()`` and, under pressure, demotes a victim problem —
``swap_out_problem`` spills every sequence of its namespace to the
engine's host-side buffer and releases the pages; ``swap_in_problem``
restores them bit-identically once retirements free room.  Demotion is
invisible to the search logic: a parked problem simply posts no demand
for a few global steps, and per-problem RNG chains make the step
timing irrelevant to its sampled streams.

``on_step`` (called by the controller after pruning) frees the engine
sequences of pruned leaves — this is where ETS's ILP decisions become
physical page releases.  It only sweeps the *owning problem's*
namespace, so concurrent problems on the same engine never free each
other's pages.  ``finish_problem`` (called by the scheduler at
retirement) releases whatever the final step left behind.  Each trace
entry carries the step's attention-IO deltas (``unique_pages_streamed``
vs ``logical_pages_streamed``); ``io_summary`` reduces them to the
measured sharing ratio.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import SearchTree
from repro.obs import spanned

from .engine import PagedEngine, pow2_bucket as _bucket

# vectorized per-branch key derivation: fold_in(step_key, branch_index)
_fold_rows = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))


@dataclass
class BackendConfig:
    step_token: int                # reasoning-step delimiter (e.g. '\n')
    eos_token: int
    max_step_tokens: int = 48
    max_depth: int = 16
    temperature: float = 1.0


@dataclass
class ExpandTicket:
    """One problem's expansion split at its decode boundary.

    Returned by ``LMBackend.expand_begin``: the leaves are already
    branched (engine sequences exist, pages reserved) and the problem's
    step key is consumed, but nothing is decoded yet.  The caller
    decodes ``branches`` with per-row ``row_keys`` on whatever schedule
    it likes (one drain-to-empty stream, or row-by-row refill of a
    persistent ``DecodeStream``) and hands the token streams to
    ``expand_finish``.  ``plan`` keeps the (leaf, branch ids) grouping
    so children come back in ``leaf_counts`` order.
    """
    tree: SearchTree
    plan: List[Tuple[int, List[int]]]
    branches: List[int]
    row_keys: Optional[jax.Array]


def _pad_bucket(seqs: Sequence[Sequence[int]]):
    """Pad token sequences into a power-of-two (rows, length) bucket.

    Returns (toks (Bp,T), pos (Bp,T), lengths (Bp,)): tokens
    zero-padded, positions -1 at pads (the attention mask treats -1 as
    an empty slot, so padding never leaks into real positions), padded
    rows given length 1.  Bucketing both dims bounds the jit-signature
    count at O(log max_batch * log max_len).
    """
    B = len(seqs)
    lens = [len(s) for s in seqs]
    T = _bucket(max(lens))
    Bp = _bucket(B, lo=1)
    toks = np.zeros((Bp, T), np.int32)
    pos = np.full((Bp, T), -1, np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
        pos[i, :len(s)] = np.arange(len(s))
    lengths = np.ones(Bp, np.int32)
    lengths[:B] = lens
    return toks, pos, lengths


def _split_counts(flat: Sequence, counts: Sequence[int]) -> List[List]:
    """Un-flatten a per-request concatenation."""
    out, i = [], 0
    for n in counts:
        out.append(list(flat[i:i + n]))
        i += n
    return out


class LMBackend:
    def __init__(self, engine: PagedEngine, prm_model, prm_params,
                 embed_model, embed_params, bcfg: BackendConfig,
                 answer_fn: Callable[[List[int]], Optional[Any]],
                 seed: int = 0):
        self.engine = engine
        self.prm_model = prm_model
        self.prm_params = prm_params
        self.embed_model = embed_model
        self.embed_params = embed_params
        self.bcfg = bcfg
        self.answer_fn = answer_fn
        self.seed = seed
        # per-problem state, keyed by namespace: sampling-key chain
        # (seeded like a fresh reset()), live engine sequences, KV/IO
        # trace, and the last sampled cumulative IO counters (the trace
        # stores per-step deltas)
        self._keys: Dict[Any, jax.Array] = {}
        self._ns_seqs: Dict[Any, set] = {}
        self.kv_trace_by_problem: Dict[Any, List[Dict[str, int]]] = {}
        self._last_io_ns: Dict[Any, Tuple[int, int]] = {}
        # generated tokens per problem, measured at the decode boundary
        # (expand_finish) — the budget controller's token ledger reads
        # this instead of re-deriving spend from the tree
        self.gen_tokens_by_problem: Dict[Any, int] = {}
        # flat trace across problems, in on_step order (solo runs see
        # exactly the pre-namespace behavior)
        self.kv_trace: List[Dict[str, int]] = []
        # roots prefilled ahead of their search (start_many sweeps):
        # on_step must not free them before their search branches them
        self._protected: set = set()
        self._score_fn = jax.jit(
            lambda p, toks: prm_model.reward(p, {"tokens": toks}))
        self._embed_fn = jax.jit(
            lambda p, toks: embed_model.hidden(p, {"tokens": toks}))
        # Bucketed batch paths.  The trace counters increment when jax
        # traces (i.e. compiles) a new signature — tests assert they stay
        # O(log max_len), not O(distinct lengths).
        self.score_traces = 0
        self.embed_traces = 0
        # PRM work of score_multi: rows scored, their true token counts,
        # and the tokens of the padded (rows, length) buckets they ran in
        self.n_scored_rows = 0
        self.n_scored_tokens = 0
        self.n_scored_padded_tokens = 0

        def score_batch(p, toks, positions, lengths):
            self.score_traces += 1      # trace-time side effect
            r = prm_model.reward(p, {"tokens": toks, "positions": positions})
            idx = jnp.clip(lengths - 1, 0, toks.shape[1] - 1)
            return jnp.take_along_axis(r, idx[:, None], axis=1)[:, 0]

        def embed_batch(p, toks, positions):
            self.embed_traces += 1      # trace-time side effect
            h = embed_model.hidden(p, {"tokens": toks,
                                       "positions": positions})
            mask = (positions >= 0).astype(h.dtype)
            denom = jnp.maximum(mask.sum(axis=1), 1.0)
            return (h * mask[:, :, None]).sum(axis=1) / denom[:, None]

        self._score_batch_fn = jax.jit(score_batch)
        self._embed_batch_fn = jax.jit(embed_batch)

    # ------------------------------------------------------------------
    def _ns_of(self, seq_id: int):
        """Problem namespace of an engine sequence (engine doubles
        without an allocator or handle namespaces fall back to the root
        seq id, which is equally unique per problem)."""
        alloc = getattr(self.engine, "alloc", None)
        h = alloc.seqs.get(seq_id) if alloc is not None else None
        return getattr(h, "ns", seq_id)

    def start(self, prompt_tokens: Sequence[int]) -> SearchTree:
        return self.start_many([prompt_tokens])[0]

    @spanned("backend.prefill")
    def start_many(self, prompts: Sequence[Sequence[int]]
                   ) -> List[SearchTree]:
        """Prefill a whole problem sweep in one batched flash stream.

        All prompts go through ``engine.prefill_many`` — one lock-step,
        length-bucketed prefill for the sweep instead of one serial
        dense prefill per problem.  Each prompt opens its own problem
        namespace (fresh sampling-key chain, own sequence set and IO
        trace).  The pending roots are protected from ``on_step``'s
        free-sweep until their own search branches them (an unstarted
        problem has no live leaf in any tree yet, so the keep-set would
        otherwise free its pages).
        """
        batch_fn = getattr(self.engine, "prefill_many", None)
        if batch_fn is not None:
            sids = batch_fn(prompts)
        else:           # minimal engine doubles: per-prompt fallback
            sids = [self.engine.prefill(p) for p in prompts]
        self._protected.update(sids)
        trees = []
        for p, sid in zip(prompts, sids):
            ns = self._ns_of(sid)
            self._keys[ns] = jax.random.key(self.seed)
            self._ns_seqs.setdefault(ns, set()).add(sid)
            trees.append(SearchTree(
                root_tokens=len(p),
                root_payload={"seq_id": sid, "tokens": [], "ns": ns}))
        return trees

    def _next_key(self, ns):
        key = self._keys.setdefault(ns, jax.random.key(self.seed))
        self._keys[ns], sub = jax.random.split(key)
        return sub

    def _add_child(self, tree: SearchTree, leaf: int, bid: int,
                   toks: List[int]) -> int:
        """Create the tree node for decoded branch `bid` of `leaf`."""
        node = tree.node(leaf)
        full = self.engine.tokens[bid]
        ans = self.answer_fn(full)
        finished = (bool(toks) and toks[-1] == self.bcfg.eos_token) \
            or ans is not None \
            or node.depth + 1 >= self.bcfg.max_depth \
            or len(full) >= self.engine.ecfg.max_seq_len - \
            self.bcfg.max_step_tokens
        return tree.add(leaf, n_tokens=len(toks), finished=finished,
                        payload={"seq_id": bid, "tokens": toks,
                                 "answer": ans})

    # -- Backend protocol --------------------------------------------------
    def expand(self, tree: SearchTree, leaf: int, n: int) -> List[int]:
        return self.expand_many(tree, [(leaf, n)])

    def expand_many(self, tree: SearchTree,
                    leaf_counts: Sequence[Tuple[int, int]]) -> List[int]:
        """Branch every live leaf, then decode all branches lock-step
        (the one-problem case of ``expand_multi``)."""
        return self.expand_multi([(tree, leaf_counts)])[0]

    # -- row-level demand interface (the serving loop's refill protocol) --
    # One expansion is split at its decode boundary: ``expand_begin``
    # does everything that must happen atomically per problem (branch
    # the leaves, consume ONE step key from the problem's chain, derive
    # per-branch row keys), ``expand_finish`` turns the decoded token
    # streams into tree children.  Between the two, the caller owns the
    # decode — ``expand_multi`` drains everything in one lock-step
    # stream, while the online serving loop feeds the same branches into
    # a persistent ``DecodeStream`` row by row as slots free up.  Row
    # keys make the schedule irrelevant: a branch's stream depends only
    # on its own key and logits, so both drivers are bit-identical.

    @spanned("backend.expand_begin")
    def expand_begin(self, tree: SearchTree,
                     leaf_counts: Sequence[Tuple[int, int]]
                     ) -> "ExpandTicket":
        """Branch a problem's live leaves and derive its row keys,
        without decoding.  Consumes one step key iff any leaf branches."""
        ns = tree.node(0).payload["ns"]
        plan: List[Tuple[int, List[int]]] = []
        branches: List[int] = []
        for leaf, n in leaf_counts:
            node = tree.node(leaf)
            if node.depth >= self.bcfg.max_depth or n <= 0:
                continue
            bids = self.engine.branch(node.payload["seq_id"], n)
            # once branched, the root's pages live on through its
            # children's refcounts — drop the sweep protection
            self._protected.discard(node.payload["seq_id"])
            self._ns_seqs.setdefault(ns, set()).update(bids)
            plan.append((leaf, bids))
            branches.extend(bids)
        row_keys = None
        if branches:
            step_key = self._next_key(ns)
            row_keys = _fold_rows(step_key,
                                  jnp.arange(len(branches), dtype=jnp.uint32))
        return ExpandTicket(tree=tree, plan=plan, branches=branches,
                            row_keys=row_keys)

    @spanned("backend.expand_finish")
    def expand_finish(self, ticket: "ExpandTicket",
                      outs: Dict[int, List[int]]) -> List[int]:
        """Turn a ticket's decoded streams (``outs``: seq id -> step
        tokens) into tree children, grouped by leaf in plan order."""
        kids: List[int] = []
        ns = ticket.tree.node(0).payload["ns"]
        for leaf, bids in ticket.plan:
            for bid in bids:
                self.gen_tokens_by_problem[ns] = \
                    self.gen_tokens_by_problem.get(ns, 0) + len(outs[bid])
                kids.append(self._add_child(ticket.tree, leaf, bid,
                                            outs[bid]))
        return kids

    def problem_gen_tokens(self, tree: SearchTree) -> int:
        """Tokens this problem's decodes have generated so far — the
        measured per-problem spend the budget controller's global token
        ledger charges against (``repro.core.controllers
        .BudgetController``)."""
        ns = tree.node(0).payload["ns"]
        return self.gen_tokens_by_problem.get(ns, 0)

    def open_stream(self):
        """A persistent row-refillable decode stream configured with
        this backend's step semantics (see ``DecodeStream``)."""
        return self.engine.open_stream(
            temperature=self.bcfg.temperature,
            stop_tokens=(self.bcfg.step_token, self.bcfg.eos_token))

    def stream_budget(self) -> int:
        """Per-row token budget of one search step."""
        return self.bcfg.max_step_tokens

    def expand_multi(self, reqs: Sequence[Tuple[SearchTree,
                                                Sequence[Tuple[int, int]]]]
                     ) -> List[List[int]]:
        """Branch every problem's live leaves, then decode the union of
        branches in ONE lock-step stream.

        One ``engine.decode`` call covers every problem's new branches;
        the combined branch list is chunked only when it exceeds
        ``max_batch``.  Each problem consumes exactly one step key from
        its own chain, and each branch samples from
        ``fold_in(step_key, branch_index)`` — so chunk boundaries and
        batch composition can't perturb any branch's token stream, and
        the sweep reproduces solo runs bit-for-bit.  Children are
        returned per request, grouped by leaf in ``leaf_counts`` order.
        """
        tickets = [self.expand_begin(tree, leaf_counts)
                   for tree, leaf_counts in reqs]
        all_branches = [b for t in tickets for b in t.branches]
        outs: Dict[int, List[int]] = {}
        if all_branches:
            key_groups = [t.row_keys for t in tickets
                          if t.row_keys is not None]
            row_keys = key_groups[0] if len(key_groups) == 1 \
                else jnp.concatenate(key_groups)
            mb = self.engine.ecfg.max_batch
            for i in range(0, len(all_branches), mb):
                outs.update(self.engine.decode(
                    all_branches[i:i + mb], self.bcfg.max_step_tokens,
                    temperature=self.bcfg.temperature,
                    stop_tokens=(self.bcfg.step_token, self.bcfg.eos_token),
                    row_keys=row_keys[i:i + mb]))
        return [self.expand_finish(t, outs) for t in tickets]

    def score(self, tree: SearchTree, node: int) -> float:
        sid = tree.node(node).payload["seq_id"]
        toks = jnp.asarray([self.engine.tokens[sid]], jnp.int32)
        r = self._score_fn(self.prm_params, toks)
        return float(r[0, -1])

    def score_many(self, tree: SearchTree,
                   nodes: Sequence[int]) -> List[float]:
        """One padded-bucket PRM call for every candidate of the step."""
        return self.score_multi([(tree, nodes)])[0]

    @spanned("backend.score")
    def score_multi(self, reqs: Sequence[Tuple[SearchTree, Sequence[int]]]
                    ) -> List[List[float]]:
        """ONE padded-bucket PRM call covering every problem's
        candidates; per-row rewards are split back per request.  Rows
        are independent under the position mask, so each problem's
        rewards match its solo ``score_many`` bit-for-bit regardless of
        how the sweep fills the bucket."""
        counts = [len(nodes) for _, nodes in reqs]
        seqs = [self.engine.tokens[tree.node(n).payload["seq_id"]]
                for tree, nodes in reqs for n in nodes]
        if not seqs:
            return [[] for _ in reqs]
        toks, pos, lengths = _pad_bucket(seqs)
        self.n_scored_rows += len(seqs)
        self.n_scored_tokens += int(lengths[:len(seqs)].sum())
        self.n_scored_padded_tokens += toks.size
        r = self._score_batch_fn(self.prm_params, jnp.asarray(toks),
                                 jnp.asarray(pos), jnp.asarray(lengths))
        flat = [float(x) for x in np.asarray(r)[:len(seqs)]]
        return _split_counts(flat, counts)

    def embed(self, tree: SearchTree, node: int) -> np.ndarray:
        step = tree.node(node).payload["tokens"]
        if not step:
            return np.zeros(self.embed_model.cfg.d_model, np.float32)
        toks = jnp.asarray([step], jnp.int32)
        h = self._embed_fn(self.embed_params, toks)
        return np.asarray(h[0].mean(axis=0), np.float32)

    def embed_many(self, tree: SearchTree,
                   nodes: Sequence[int]) -> np.ndarray:
        """Bucketed batch embed; padding is masked out of the encoder's
        attention (positions == -1) and of the mean pool."""
        return self.embed_multi([(tree, nodes)])[0]

    @spanned("backend.embed")
    def embed_multi(self, reqs: Sequence[Tuple[SearchTree, Sequence[int]]]
                    ) -> List[np.ndarray]:
        """ONE bucketed encoder call covering every problem's nodes."""
        d = self.embed_model.cfg.d_model
        counts = [len(nodes) for _, nodes in reqs]
        steps = [tree.node(n).payload["tokens"]
                 for tree, nodes in reqs for n in nodes]
        out = np.zeros((len(steps), d), np.float32)
        idx = [i for i, s in enumerate(steps) if s]
        if idx:
            toks, pos, _ = _pad_bucket([steps[i] for i in idx])
            h = self._embed_batch_fn(self.embed_params, jnp.asarray(toks),
                                     jnp.asarray(pos))
            h = np.asarray(h, np.float32)
            for row, i in enumerate(idx):
                out[i] = h[row]
        return np.split(out, np.cumsum(counts)[:-1])

    def answer(self, tree: SearchTree, leaf: int) -> Any:
        return tree.node(leaf).payload.get("answer")

    # -- lifecycle -----------------------------------------------------
    def _ns_stats(self, ns) -> Dict[str, int]:
        """This problem's page accounting (falls back to the engine's
        global stats on engine doubles without namespace support)."""
        fn = getattr(getattr(self.engine, "alloc", None),
                     "ns_page_stats", None)
        if fn is None:
            stats = dict(self.engine.kv_stats())
            stats.pop("unique_pages_streamed", None)
            stats.pop("logical_pages_streamed", None)
            return stats
        # pass our own live-sequence set: O(this problem's sequences),
        # not O(every sequence in the allocator), per step
        return fn(ns, seq_ids=sorted(self._ns_seqs.get(ns, ())))

    @spanned("backend.release")
    def on_step(self, tree: SearchTree, live: Sequence[int]) -> None:
        """Free engine sequences of pruned/finished leaves; sample stats.

        Only sweeps the owning problem's namespace: live leaves keep
        their sequences (interior nodes' pages stay alive through their
        descendants' block-table refcounts), pending start_many roots
        stay protected until branched, and other problems sharing the
        engine are never touched.
        """
        ns = tree.node(0).payload["ns"]
        keep = set(self._protected)
        for leaf in live:
            pl = tree.node(leaf).payload
            if pl and "seq_id" in pl:
                keep.add(pl["seq_id"])
        pool = self._ns_seqs.get(ns, set())
        for sid in sorted(pool - keep):
            if sid in self.engine.alloc.seqs:
                self.engine.free(sid)
            pool.discard(sid)
        stats = self._ns_stats(ns)
        # convert the engine's cumulative per-problem IO counters to
        # per-step deltas (what this step's decode actually streamed
        # *for this problem*)
        uniq = getattr(self.engine, "unique_pages_streamed_by_ns",
                       {}).get(ns, 0)
        logical = getattr(self.engine, "logical_pages_streamed_by_ns",
                          {}).get(ns, 0)
        last = self._last_io_ns.get(ns, (0, 0))
        stats["unique_pages_streamed"] = uniq - last[0]
        stats["logical_pages_streamed"] = logical - last[1]
        self._last_io_ns[ns] = (uniq, logical)
        self.kv_trace.append(stats)
        self.kv_trace_by_problem.setdefault(ns, []).append(stats)

    def io_summary(self, ns=None) -> Dict[str, float]:
        """Measured attention-IO over the recorded steps: pages streamed
        per decode step and the realized sharing ratio (>1 whenever
        branches share prefix pages and the engine runs tree attention).
        ``ns`` selects one problem's trace (what ``SearchResult.kv_summary``
        reports in a sweep); without it the reduction covers every
        problem recorded since the last reset."""
        trace = self.kv_trace if ns is None \
            else self.kv_trace_by_problem.get(ns, [])
        uniq = sum(t.get("unique_pages_streamed", 0) for t in trace)
        logical = sum(t.get("logical_pages_streamed", 0) for t in trace)
        steps = max(len(trace), 1)
        return {
            "unique_pages_streamed": uniq,
            "logical_pages_streamed": logical,
            "pages_streamed_per_step": uniq / steps,
            "io_sharing_ratio": logical / max(uniq, 1),
        }

    # -- memory pressure (the scheduler's admission/demotion protocol) --
    # The sweep scheduler reserves a working-set estimate per problem at
    # admission and demotes (swaps out) victims under pressure; these
    # methods are the backend half of that contract.  All page units.

    def capacity(self) -> Optional[Dict[str, int]]:
        """Pool capacity: total allocatable pages and currently free.
        ``None`` on engine doubles without an allocator or swap support
        — the scheduler then runs without pressure management."""
        alloc = getattr(self.engine, "alloc", None)
        if alloc is None or not hasattr(self.engine, "swap_out"):
            return None
        return {"total_pages": alloc.n_pages,
                "free_pages": len(alloc.free)}

    def prompt_pages(self, prompt_tokens: Sequence[int]) -> int:
        """Pages one prompt's prefill holds (``tokens[:-1]`` in pages,
        rounded up so the pending token's first append is covered)."""
        ps = self.engine.ecfg.page_size
        return max(-(-len(prompt_tokens) // ps), 1)

    def step_pages_per_branch(self) -> int:
        """Worst-case page growth of ONE branch over ONE search step:
        a CoW of the shared last page plus pages for the step's new
        tokens.  Tight: a step appends at most ``max_step_tokens``
        slots, and from any starting fill that allocates at most
        ``ceil(max_step_tokens / page_size)`` fresh pages on top of the
        privatized one."""
        ps = self.engine.ecfg.page_size
        return 1 + -(-self.bcfg.max_step_tokens // ps)

    def problem_pages(self, tree: SearchTree) -> int:
        """Physical pages this problem holds right now."""
        ns = tree.node(0).payload["ns"]
        return self._ns_stats(ns).get("physical_pages", 0)

    def problem_swapped_pages(self, tree: SearchTree) -> int:
        """Pages this problem has parked in the host spill buffer."""
        ns = tree.node(0).payload["ns"]
        return self._ns_stats(ns).get("swapped_pages", 0)

    def swap_out_problem(self, tree: SearchTree,
                         need_pages: Optional[int] = None) -> int:
        """Demote one problem: spill its engine sequences' pages to the
        host buffer and release them (``engine.swap_out``).  The
        problem's search state parks until ``swap_in_problem``.

        With ``need_pages`` set (subtree-grained spill), only enough
        sequences to release at least that many pages are demoted — a
        greedy pick maximizing released pages per sequence, so a small
        deficit spills a subtree of leaves (their exclusive pages below
        the fork) while the shared prefix and the rest of the problem's
        KV stay hot in the pool.  The whole problem still parks; resume
        traffic is just proportionally smaller.
        """
        ns = tree.node(0).payload["ns"]
        ids = sorted(self._ns_seqs.get(ns, ()))
        if need_pages is not None and ids:
            chosen = self._pick_spill_subset(ids, need_pages)
            if len(chosen) < len(ids):
                return self.engine.swap_out(chosen, partial=True)
        return self.engine.swap_out(ids)

    def _pick_spill_subset(self, ids: Sequence[int],
                           need_pages: int) -> List[int]:
        """Greedy subset selection for a partial demotion: repeatedly
        add the sequence that releases the most additional pages (pages
        whose every reference falls inside the chosen set), smallest
        seq id on ties, until ``need_pages`` pages free.  Deterministic
        given the allocator state, so pressured sweeps stay
        reproducible."""
        alloc = self.engine.alloc
        chosen: List[int] = []
        in_set: Dict[int, int] = {}
        released = 0
        remaining = list(ids)
        while remaining and released < need_pages:
            best, best_gain = None, -1
            for s in remaining:
                gain = 0
                seen: Dict[int, int] = {}
                for pg in alloc.seqs[s].block_table:
                    seen[pg] = seen.get(pg, 0) + 1
                for pg, n in seen.items():
                    if in_set.get(pg, 0) + n == alloc.refcount[pg]:
                        gain += 1
                if gain > best_gain:
                    best, best_gain = s, gain
            chosen.append(best)
            remaining.remove(best)
            for pg in alloc.seqs[best].block_table:
                in_set[pg] = in_set.get(pg, 0) + 1
            released += best_gain
        return chosen

    def swap_in_problem(self, tree: SearchTree) -> int:
        """Restore a demoted problem's pages (exact copies — its decode
        streams resume bit-identically).  Raises ``OutOfPages`` and
        leaves the problem parked when the pool still lacks room.  Only
        the problem's *swapped* sequences restore — after a
        subtree-grained demotion the rest never left the pool."""
        ns = tree.node(0).payload["ns"]
        seqs = self.engine.alloc.seqs
        ids = [s for s in sorted(self._ns_seqs.get(ns, ()))
               if s in seqs and seqs[s].swapped]
        return self.engine.swap_in(ids)

    def finish_problem(self, tree: SearchTree) -> None:
        """Retire one problem: free whatever engine sequences its final
        step left behind (unbranched roots included) and drop its
        per-problem RNG/sequence bookkeeping plus the engine's per-ns
        IO counters (no further decode can touch the namespace).  The
        KV/IO traces (``kv_trace_by_problem``) are deliberately kept —
        the benchmarks and the fig2 validation read them after
        retirement; a long-lived server should ``reset()`` between
        measurement windows to reclaim them.  Called by the sweep
        scheduler; solo callers may keep using ``reset()`` between
        problems instead.
        """
        pl = tree.node(0).payload
        ns = pl.get("ns") if isinstance(pl, dict) else None
        if ns is None:        # not a tree this backend started
            return
        for sid in sorted(self._ns_seqs.pop(ns, set())):
            self._protected.discard(sid)
            if sid in self.engine.alloc.seqs:
                self.engine.free(sid)
        self._keys.pop(ns, None)
        self._last_io_ns.pop(ns, None)
        getattr(self.engine, "unique_pages_streamed_by_ns", {}).pop(ns, None)
        getattr(self.engine, "logical_pages_streamed_by_ns", {}).pop(ns,
                                                                    None)

    def reset(self) -> None:
        """Reset for an independent stream of problems on the same
        backend: frees every engine sequence, clears every per-problem
        KV/IO trace and sampling-key chain, and zeroes the engine
        throughput/IO counters — so successive runs neither mix KV
        traces nor leak RNG state.  Jit caches (decode/prefill/bucketed
        PRM + embedder) and the jit-trace counters (``score_traces``
        etc., which track cache lifetime, not per-problem state) survive
        untouched.

        .. deprecated::
            Problem namespaces made the blanket reset vestigial: every
            search tree lives in its own namespace and ``run_search``
            frees it on exit, so independent problems never share KV or
            RNG state to begin with.  For benchmark measurement windows
            call ``engine.reset_counters()`` directly.  ``reset()`` will
            be removed in a future release."""
        warnings.warn(
            "LMBackend.reset() is deprecated: per-problem namespaces "
            "already isolate searches (run_search frees its tree on "
            "exit); use engine.reset_counters() to delimit measurement "
            "windows. reset() will be removed in a future release.",
            DeprecationWarning, stacklevel=2)
        self.engine.reset()
        if hasattr(self.engine, "reset_counters"):
            self.engine.reset_counters()
        self._protected.clear()
        self.kv_trace.clear()
        self.kv_trace_by_problem.clear()
        self.gen_tokens_by_problem.clear()
        self._keys.clear()
        self._ns_seqs.clear()
        self._last_io_ns.clear()
