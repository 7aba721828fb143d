"""90th percentile of the time from each request's due time to its
answer, over every request sent in the window (harness stamps, host
clock); an unanswered request counts at its wait so far."""


def read(ctx):
    tta = ctx["tta_s"]
    return ctx["percentile"](tta, 90) if tta else None
