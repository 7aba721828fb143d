"""Device time of the tree decode program (``step``) per lock-step
decode iteration, in the traced window."""
from benchmarks.chip import trace as TR

PROGRAM = "step"


def read(ctx):
    n = ctx["counters"]["n_decode_steps"]
    s = TR.program_s(ctx["trace"], PROGRAM) if n else None
    return None if s is None else 1e3 * s / n
