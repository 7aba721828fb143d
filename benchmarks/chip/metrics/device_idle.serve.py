"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    t = ctx["trace"]
    return None if not t else 100.0 * t["idle_share"]
