"""Reduce a profiler trace to device busy time, program and kernel time,
and idle gaps named by what the host was doing.

A trace is held as plain data: ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``.
:func:`from_xplane` makes it from the ``.xplane.pb`` file that
``jax.profiler`` writes.  Device planes are named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per executed operation and their
``XLA Modules`` line one per executed program (``jit_<name>(<id>)``).
Host spans of the benchmark are named ``bench.<layer>`` and the
measured window is the span ``bench.window``.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def from_xplane(path: str) -> dict:
    """Plain-data copy of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def is_device(plane: dict) -> bool:
    return re.fullmatch(r"/device:TPU:\d+", plane["name"]) is not None


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def window_of(trace: dict) -> Optional[Tuple[int, int]]:
    """(start, end) ns of the benchmark's window span, if traced."""
    for plane in trace["planes"]:
        if is_device(plane):
            continue
        for line in plane["lines"]:
            for name, t, d in line["events"]:
                if name == WINDOW_SPAN:
                    return t, t + d
    return None


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(t, d, lo, hi):
    a, b = max(t, lo), min(t + d, hi)
    return (a, b) if b > a else None


def program_name(event_name: str) -> str:
    """``jit_step(1234)`` -> ``step``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def reduce(trace: dict, window: Optional[Tuple[int, int]] = None) -> dict:
    """Busy union, idle share, time per program, per operation, and
    idle gaps by host span, all inside ``window`` (default: the
    ``bench.window`` span, else the extent of device activity)."""
    devices = [p for p in trace["planes"] if is_device(p)
               and _line(p, OPS_LINE)]
    if window is None:
        window = window_of(trace)
    if window is None:
        ev = [(t, t + d) for p in devices for _, t, d in _line(p, OPS_LINE)]
        window = (min(a for a, _ in ev), max(b for _, b in ev))
    lo, hi = window
    busy_per_dev, unions = [], []
    ops: Dict[str, int] = defaultdict(int)
    programs: Dict[str, int] = defaultdict(int)
    program_calls: Dict[str, int] = defaultdict(int)
    for p in devices:
        iv = []
        for name, t, d in _line(p, OPS_LINE):
            c = _clip(t, d, lo, hi)
            if c:
                iv.append(c)
                ops[name] += c[1] - c[0]
        u = union(iv)
        unions.append(u)
        busy_per_dev.append(sum(b - a for a, b in u))
        for name, t, d in _line(p, MODULES_LINE):
            c = _clip(t, d, lo, hi)
            if c:
                programs[program_name(name)] += c[1] - c[0]
                program_calls[program_name(name)] += 1
    n = max(len(devices), 1)
    busy_ns = sum(busy_per_dev) / n
    window_ns = hi - lo
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
        "n_devices": len(devices),
        "ops_s": {k: v / 1e9 / n for k, v in ops.items()},
        "programs_s": {k: v / 1e9 / n for k, v in programs.items()},
        "program_calls": {k: v // n for k, v in program_calls.items()},
        "idle_by_span_s": idle_by_span(trace, unions[0] if unions else [],
                                       lo, hi),
    }


def _host_spans(trace: dict) -> List[Tuple[int, int, str]]:
    spans = []
    for plane in trace["planes"]:
        if is_device(plane):
            continue
        for line in plane["lines"]:
            for name, t, d in line["events"]:
                if name.startswith(SPAN_PREFIX) and name != WINDOW_SPAN:
                    spans.append((t, t + d, name[len(SPAN_PREFIX):]))
    return spans


def idle_by_span(trace: dict, busy: List[Tuple[int, int]], lo: int,
                 hi: int) -> Dict[str, float]:
    """Seconds of device idle time inside [lo, hi), split by the
    innermost benchmark host span open at each moment (``host`` where
    none is)."""
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, min(a, hi)))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    # sweep over every span and gap edge; each elementary piece of a
    # gap goes to the shortest span open over it
    edges = []
    for i, (a, b) in enumerate(gaps):
        edges += [(a, 1, i, None), (b, 0, i, None)]
    for a, b, name in _host_spans(trace):
        if b > lo and a < hi:
            edges += [(a, 3, b - a, name), (b, 2, b - a, name)]
    edges.sort(key=lambda e: (e[0], e[1]))
    out: Dict[str, float] = defaultdict(float)
    open_spans: Dict[Tuple[int, str], int] = defaultdict(int)
    in_gap, prev = False, None
    for t, kind, key, name in edges:
        if in_gap and prev is not None and t > prev:
            out[min(open_spans)[1] if open_spans else "host"] += (t - prev) / 1e9
        prev = t
        if kind == 1:
            in_gap = True
        elif kind == 0:
            in_gap = False
        elif kind == 3:
            open_spans[(key, name)] += 1
        else:
            open_spans[(key, name)] -= 1
            if not open_spans[(key, name)]:
                del open_spans[(key, name)]
    return dict(out)


def program_s(reduced: Optional[dict], name: str) -> Optional[float]:
    """Device seconds of the program ``name`` in a reduced trace; None
    where no device was traced.  A device trace without the program
    raises: the program was renamed, and the reader has to follow."""
    if not reduced or not reduced["n_devices"]:
        return None
    if name not in reduced["programs_s"]:
        raise KeyError(f"no program {name!r} in the device trace; it has "
                       f"{sorted(reduced['programs_s'])}")
    return reduced["programs_s"][name]


def op_name(event_name: str) -> str:
    """An operation's own name: ``%tree_attention.2 = f32[...] ...`` ->
    ``tree_attention.2``.  The rest of the event's text names its
    operands, which may be the outputs of other operations."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kernel_s(reduced: Optional[dict], name: str) -> Optional[float]:
    """Device seconds of the operations named ``name`` or
    ``name.<n>`` (one per call site); None where no device was traced,
    and raises where a device trace has none of them."""
    if not reduced or not reduced["n_devices"]:
        return None
    own = re.compile(re.escape(name) + r"(\.\d+)?")
    secs = [v for k, v in reduced["ops_s"].items()
            if own.fullmatch(op_name(k))]
    if not secs:
        raise KeyError(f"no operation {name!r} in the device trace")
    return sum(secs)


def top(d: Dict[str, float], k: int = 10, width: int = 120) -> List[list]:
    """The ``k`` largest entries, each name cut to ``width`` characters
    (an operation's text lists every operand)."""
    return [[n if len(n) <= width else n[:width - 3] + "...", v]
            for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def excerpt(trace: dict, start: int, end: int) -> dict:
    """The events of ``trace`` that overlap [start, end), with every
    line kept (empty where nothing overlaps)."""
    return {"planes": [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [
            e for e in ln["events"] if e[1] < end and e[1] + e[2] > start]}
        for ln in p["lines"]]} for p in trace["planes"]]}
