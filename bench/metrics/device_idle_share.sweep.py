"""Share of the traced sweep window in which no operation ran on the
chip (1 - busy / window, busy the union of operation intervals)."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share()
