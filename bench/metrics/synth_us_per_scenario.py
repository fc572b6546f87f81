"""Device time of waveform synthesis (``_synth_vmapped``) per scenario
of the traced window."""


def read(ctx):
    s = ctx.program_s("_synth_vmapped")
    n = ctx.stats.get("rows_dispatched")
    return None if not s or not n else 1e6 * s / n
