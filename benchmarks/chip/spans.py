"""Reduce the program's own host spans in a profiler trace.

The serving stack records spans named ``repro.<layer>.<part>`` (see
``src/repro/obs.py``) in the same trace, on the same clock, as the
device's programs.  :func:`reduce` turns those inside the benchmark's
window (the span ``bench.window``, else the extent of device activity)
into, per span name:

- ``n``: spans that overlap the window;
- ``total_s``: their time inside the window;
- ``self_s``: that time less the time of the ``repro.*`` spans nested
  directly in them;
- ``max_s``: the longest one;
- ``idle_s``: device idle time put on the innermost ``repro.*`` span
  open at each moment, the way ``trace.idle_by_span`` puts it on the
  innermost ``bench.*`` span.

Idle time under no ``repro.*`` span is ``idle_outside_s``;
``idle_by_bench`` splits the idle time by innermost ``bench.*`` span
(``host`` where none is, as ``trace.py`` names it) and, within it, by
innermost ``repro.*`` span (:data:`NONE` where none is).

The functions below :func:`reduce` are the per-layer readings the
spans and the program's counters give.  Each returns None where it
finds nothing to read: a program without these spans or counters.

The trace is the plain-data form of ``trace.from_xplane``.
"""
from __future__ import annotations

import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace as TR

PREFIX = "repro."
NONE = "(none)"


def _device_busy(trace: dict, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Busy union of the first traced device inside [lo, hi), as
    ``trace.reduce`` takes it for idle gaps."""
    for p in trace["planes"]:
        if TR.is_device(p) and TR._line(p, TR.OPS_LINE):
            return TR.union([c for _, t, d in TR._line(p, TR.OPS_LINE)
                             if (c := TR._clip(t, d, lo, hi))])
    return []


def _window(trace: dict) -> Tuple[int, int]:
    w = TR.window_of(trace)
    if w is not None:
        return w
    ev = [(t, t + d) for p in trace["planes"] if TR.is_device(p)
          for _, t, d in TR._line(p, TR.OPS_LINE)]
    return min(a for a, _ in ev), max(b for _, b in ev)


def _host_spans(trace: dict, lo: int, hi: int, want):
    """Host spans whose name passes ``want``, clipped to [lo, hi), per
    host line: ``[[(start, end, name), ...], ...]``."""
    out = []
    for plane in trace["planes"]:
        if TR.is_device(plane):
            continue
        for line in plane["lines"]:
            spans = [(c[0], c[1], name) for name, t, d in line["events"]
                     if want(name) and (c := TR._clip(t, d, lo, hi))]
            if spans:
                out.append(spans)
    return out


def _is_bench(name: str) -> bool:
    return name.startswith(TR.SPAN_PREFIX) and name != TR.WINDOW_SPAN


def _is_program(name: str) -> bool:
    return name.startswith(PREFIX)


def reduce(trace: dict, window: Optional[Tuple[int, int]] = None) -> dict:
    """Per-name statistics of the ``repro.*`` spans inside ``window``
    (see the module's docstring), in seconds."""
    lo, hi = window if window is not None else _window(trace)
    lines = _host_spans(trace, lo, hi, _is_program)
    stats: Dict[str, dict] = {}
    for spans in lines:
        for a, b, name in spans:
            st = stats.setdefault(name[len(PREFIX):], {
                "n": 0, "total_s": 0.0, "self_s": 0.0, "max_s": 0.0,
                "idle_s": 0.0})
            st["n"] += 1
            st["total_s"] += (b - a) / 1e9
            st["max_s"] = max(st["max_s"], (b - a) / 1e9)
        _self_times(spans, stats)
    idle, outside, by_bench = _idle(trace, lo, hi, lines, stats)
    return {"window_s": (hi - lo) / 1e9, "spans": stats, "idle_s": idle,
            "idle_outside_s": outside, "idle_by_bench": by_bench}


def _self_times(spans, stats) -> None:
    """``self_s``: each span's time less that of its direct children.
    The spans of one thread nest, so a stack holds the open ancestors."""
    stack: List[list] = []
    done = []
    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            done.append(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([a, b, name, 0])
    done += stack
    for a, b, name, child in done:
        stats[name[len(PREFIX):]]["self_s"] += (b - a - child) / 1e9


def _idle(trace, lo, hi, lines, stats):
    """Device idle inside [lo, hi) by innermost ``repro.*`` span, and
    by innermost ``bench.*`` span crossed with it."""
    gaps, cur = [], lo
    for a, b in _device_busy(trace, lo, hi):
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    edges = []
    for a, b in gaps:
        edges += [(a, 1, None, None), (b, 0, None, None)]
    for group, spans in (("repro", [s for ln in lines for s in ln]),
                         ("bench", [s for ln in _host_spans(
                             trace, lo, hi, _is_bench) for s in ln])):
        for a, b, name in spans:
            edges += [(a, 3, group, (b - a, name)),
                      (b, 2, group, (b - a, name))]
    edges.sort(key=lambda e: (e[0], e[1]))
    open_: Dict[str, Dict[tuple, int]] = {"repro": defaultdict(int),
                                          "bench": defaultdict(int)}
    by_bench: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    idle = outside = 0.0
    in_gap, prev = False, None
    for t, kind, group, key in edges:
        if in_gap and prev is not None and t > prev:
            s = (t - prev) / 1e9
            idle += s
            prog = open_["repro"]
            bench = open_["bench"]
            p = min(prog)[1][len(PREFIX):] if prog else NONE
            b = min(bench)[1][len(TR.SPAN_PREFIX):] if bench else "host"
            if prog:
                stats[p]["idle_s"] += s
            else:
                outside += s
            by_bench[b][p] += s
        prev = t
        if kind == 1:
            in_gap = True
        elif kind == 0:
            in_gap = False
        elif kind == 3:
            open_[group][key] += 1
        else:
            open_[group][key] -= 1
            if not open_[group][key]:
                del open_[group][key]
    return idle, outside, {b: dict(v) for b, v in by_bench.items()}


def program_idle_gaps(red: Optional[dict], k: int = 10) -> List[list]:
    """The ``k`` spans with the most device idle under them."""
    if not red or not red["spans"]:
        return []
    return TR.top({n: s["idle_s"] for n, s in red["spans"].items()}, k)


# ---------------------------------------------------------------------------
# Readings
# ---------------------------------------------------------------------------

def _span(red: Optional[dict], name: str) -> Optional[dict]:
    if not red or not red["spans"]:
        return None
    return red["spans"].get(name)


def decode_host_ms_per_iter(red: Optional[dict]) -> Optional[float]:
    """Host time of one decode iteration outside its wait on the
    device: mean ``engine.decode`` less its ``engine.decode.wait``."""
    dec = _span(red, "engine.decode")
    if not dec or not dec["n"]:
        return None
    wait = red["spans"].get("engine.decode.wait", {"total_s": 0.0})
    return 1e3 * (dec["total_s"] - wait["total_s"]) / dec["n"]


def select_ms_per_step(red: Optional[dict]) -> Optional[float]:
    """Mean ``search.select``: one problem's retention decision."""
    sel = _span(red, "search.select")
    return 1e3 * sel["total_s"] / sel["n"] if sel and sel["n"] else None


def tick_max_ms(red: Optional[dict]) -> Optional[float]:
    """The longest ``loop.tick`` in the window."""
    tick = _span(red, "loop.tick")
    return 1e3 * tick["max_s"] if tick and tick["n"] else None


def gc_ms(red: Optional[dict]) -> Optional[float]:
    """Time in Python's collector in the window (0 where the program
    traced spans and no collection ran)."""
    if _span(red, "loop.tick") is None:
        return None
    gc = red["spans"].get("runtime.gc")
    return 1e3 * gc["total_s"] if gc else 0.0


PRM_COUNTERS = ("n_scored_rows", "n_scored_tokens", "n_scored_padded_tokens")


def prm_token_use(counters: Dict[str, int]) -> Optional[float]:
    """Share of the PRM's padded bucket tokens that are real tokens."""
    padded = counters.get("n_scored_padded_tokens")
    if not padded:
        return None
    return 100.0 * counters["n_scored_tokens"] / padded


def admit_wait_p50_s(submitted: Dict[int, float],
                     admitted: Dict[int, float]) -> Optional[float]:
    """Median host time from the loop being handed a request to its
    admission, over the requests admitted (the loop's own stamps)."""
    waits = [admitted[i] - submitted[i] for i in admitted if i in submitted]
    return statistics.median(waits) if waits else None
