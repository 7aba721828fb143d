"""Host spans of the serving stack, recorded in the JAX profiler's trace.

Every layer boundary on the host path opens a span named
``repro.<layer>.<part>`` (:data:`SPANS` lists them all).  A span is a
``jax.profiler.TraceAnnotation``: while a profiler session is active
(``jax.profiler.trace`` / ``start_trace``) it lands in the same trace,
on the same clock, as the device's programs and operations, so an idle
gap on the device can be put on what the host was doing at that
moment.  With no session active a span costs one TraceMe construction
and records nothing; there is no switch of its own.

Span names carry no arguments, so a reduction matches them exactly.
Spans are opened only in host code, never inside a jitted function.
"""
from __future__ import annotations

import contextlib
import functools
import gc
from typing import Iterator, List

from jax.profiler import TraceAnnotation, annotate_function

PREFIX = "repro."

SPANS = (
    "loop.tick",               # one scheduling quantum of the serving loop
    "loop.admit",              # admission waves, reservations, prefill
    "loop.pressure",           # resume, page peaks, demotion
    "loop.seat",               # demand posting and seating rows in the stream
    "loop.retire",             # a finished problem's result and release
    "search.select",           # one problem's retention policy + on_step
    "ets.cluster",             # semantic clustering of the candidates
    "ets.ilp",                 # the selection program
    "backend.release",         # frees and per-problem KV stats (on_step)
    "backend.prefill",         # prompt prefill of an admission wave
    "backend.expand_begin",    # branching and row keys
    "backend.expand_finish",   # decoded streams into tree children
    "backend.score",           # PRM padding, dispatch and readback
    "backend.embed",           # embedder padding, dispatch and readback
    "engine.decode",           # one lock-step decode iteration
    "engine.decode.reserve",   # page reservation and copy-on-write
    "engine.decode.metadata",  # row arrays and tree metadata on the host
    "engine.decode.launch",    # device puts, the decode and sample dispatch
    "engine.decode.wait",      # the host blocked on the sampled tokens
    "engine.decode.commit",    # token appends, stop checks, slot frees
    "engine.state.copy",       # recurrent-state pages copied on branch
    "runtime.gc",              # Python's cyclic collector (gc_spans only)
)


def span(name: str) -> TraceAnnotation:
    """The host span ``repro.<name>``; use it as a context manager."""
    return TraceAnnotation(PREFIX + name)


def spanned(name: str):
    """Decorator: every call of the function inside ``span(name)``."""
    return functools.partial(annotate_function, name=PREFIX + name)


@contextlib.contextmanager
def gc_spans() -> Iterator[None]:
    """While open, every run of Python's cyclic collector is recorded as
    the span ``repro.runtime.gc``, so a host stall it causes shows in a
    trace by name.  The ``gc.callbacks`` hook is removed on exit."""
    running: List[TraceAnnotation] = []

    def hook(phase, info):
        if phase == "start":
            s = span("runtime.gc")
            s.__enter__()
            running.append(s)
        elif running:
            running.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
        while running:
            running.pop().__exit__(None, None, None)
