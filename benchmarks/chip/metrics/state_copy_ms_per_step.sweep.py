"""Device time of the state pool's copy-on-branch program
(``_state_copy``, kvcache/pool.py ``StatePool.copy_page``) per search
step of one problem, in the traced window.  A program that keeps no
recurrent state, or never branched in the window, has nothing to read."""

PROGRAM = "_state_copy"


def read(ctx):
    t, n = ctx["trace"], ctx["problem_steps"]
    if not t or not t["n_devices"] or not n:
        return None
    s = t["programs_s"].get(PROGRAM)
    return None if s is None else 1e3 * s / n
