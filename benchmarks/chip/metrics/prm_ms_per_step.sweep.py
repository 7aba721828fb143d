"""Device time of the PRM's ``score_batch`` program per search step of
one problem, in the traced window."""
from benchmarks.chip import trace as TR

PROGRAM = "score_batch"


def read(ctx):
    n = ctx["problem_steps"]
    s = TR.program_s(ctx["trace"], PROGRAM) if n else None
    return None if s is None else 1e3 * s / n
