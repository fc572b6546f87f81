"""Device time of the online detector's programs (``_monitor_seg_v2``,
``_monitor_tail``) per control tick of the traced window."""


def read(ctx):
    s = ctx.program_s("_monitor_seg_v2") + ctx.program_s("_monitor_tail")
    n = ctx.stats.get("ticks")
    return None if not s or not n else 1e6 * s / n
