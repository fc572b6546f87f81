"""Device time of the spec and spectra analysis (``_analyze_vmapped``)
per scenario of the traced window."""


def read(ctx):
    s = ctx.program_s("_analyze_vmapped")
    n = ctx.stats.get("rows_dispatched")
    return None if not s or not n else 1e6 * s / n
