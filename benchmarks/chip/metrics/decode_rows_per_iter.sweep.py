"""Decode rows per lock-step iteration in the window: tokens decoded
over decode iterations (engine counters)."""


def read(ctx):
    c = ctx["counters"]
    if not c["n_decode_steps"]:
        return None
    return c["n_decoded_tokens"] / c["n_decode_steps"]
