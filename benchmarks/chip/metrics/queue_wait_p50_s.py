"""Median wait of a served request from its due time to the tick after
which the serving loop holds it live (harness stamps, host clock)."""


def read(ctx):
    waits = ctx["queue_wait_s"]
    return ctx["median"](waits) if waits else None
