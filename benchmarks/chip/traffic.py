"""The one traffic generator: a mix file's parameters and a seed in,
prompts and due times out.

A mix is a JSON file under ``traffic/``.  Every seed gets the same
multiset of prompt lengths and inter-arrival gaps (quantiles of the
stated distributions), in a seeded order, with seeded token ids: the
seed changes which request carries which size, never how much work a
run holds.  A closed backlog is longer than a window gets through, so
it is made of blocks of ``max_live`` prompts, each block the same
``max_live`` lengths spread over the range in a seeded order: the
problems live together hold the same work for every seed, however far
into the backlog a window gets.

Keys of a mix file:

- ``mode``: ``"closed"`` (``max_live`` problems in flight, refilled
  from a backlog of ``backlog`` prompts) or ``"open"`` (Poisson
  arrivals at ``rate_per_s``);
- ``prompt_len``: ``[lo, hi]``, lengths spread evenly over the range;
- ``shared_prefix_len`` (optional, default 0): every prompt of a run
  starts with the same seeded prefix of that many tokens, before its
  own ``prompt_len`` tokens (a few-shot preamble);
- ``burst`` (optional, open mode): ``{"on_s": a, "off_s": b}``,
  arrivals only in the first ``a`` seconds of every ``a + b``, at the
  rate that keeps the mean over a whole period at ``rate_per_s``;
- ``width``, ``max_steps``, ``step_tokens``, ``max_live``,
  ``temperature``, ``ets``: the search each request asks for;
- ``check``: how many finished requests, PRM rows and embedder rows
  the correctness comparison samples.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Sequence

import numpy as np


@dataclass
class Traffic:
    prompts: List[List[int]]
    due_s: List[float]          # offset from the window start (open mode)


def sub_seed(seed: int, stream: int) -> int:
    """A 32-bit seed for one named stream of randomness (weights,
    sampling, traffic), derived from the run's ``--seed``."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 63, int(stream)])
    return int(ss.generate_state(1, np.uint32)[0])


def spread(lo: float, hi: float, n: int) -> np.ndarray:
    """``n`` values spread evenly over ``[lo, hi]`` (midpoints of n
    equal bins)."""
    return lo + (hi - lo) * (np.arange(n) + 0.5) / n


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` quantile midpoints of an exponential distribution of
    mean ``1/rate``: a Poisson process's gaps, with the same sum for
    every seed."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def prompt_range(mix: dict) -> tuple:
    """Shortest and longest whole prompt of the mix, shared prefix
    included."""
    lo, hi = mix["prompt_len"]
    p = int(mix.get("shared_prefix_len", 0))
    return lo + p, hi + p


def n_requests(mix: dict, seconds: float) -> int:
    if mix["mode"] == "closed":
        return int(mix["backlog"])
    return max(int(math.floor(mix["rate_per_s"] * seconds)), 1)


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             reserved: Sequence[int] = ()) -> Traffic:
    """Prompts (token ids drawn uniformly from ``vocab`` minus the
    ``reserved`` ids) and due times for one run."""
    rng = np.random.default_rng(sub_seed(seed, 3))
    n = n_requests(mix, seconds)
    lo, hi = mix["prompt_len"]
    if mix["mode"] == "closed":
        k = int(mix["max_live"])
        block = np.rint(spread(lo, hi, k)).astype(int)
        lengths = np.concatenate([block[rng.permutation(k)]
                                  for _ in range(-(-n // k))])[:n]
    else:
        lengths = np.rint(spread(lo, hi, n)).astype(int)
        lengths = lengths[rng.permutation(n)]
    allowed = np.setdiff1d(np.arange(vocab), np.asarray(reserved, int))
    prefix = allowed[rng.integers(0, len(allowed),
                                  size=int(mix.get("shared_prefix_len", 0)))]
    prompts = [prefix.tolist()
               + allowed[rng.integers(0, len(allowed), size=int(m))].tolist()
               for m in lengths]
    if mix["mode"] == "closed":
        return Traffic(prompts, [0.0] * n)
    gaps = exp_gaps(float(mix["rate_per_s"]), n)[rng.permutation(n)]
    due = np.cumsum(gaps)
    if "burst" in mix:
        on, off = float(mix["burst"]["on_s"]), float(mix["burst"]["off_s"])
        # the same arrivals on a clock that runs only while bursts are on
        active = due * on / (on + off)
        due = np.floor(active / on) * (on + off) + np.mod(active, on)
    return Traffic(prompts, due.tolist())
