"""XLA compile time per intervention dispatch, read from the program's
own spans (``repro.obs``) of the traced window: the compile seconds
recorded inside ``repro.control.dispatch`` spans, in ms, over the number
of those spans.

The note lists the window's compiles by the innermost span around each,
with count and seconds.  A program without the span recorder reads
nothing."""
import collections

DISPATCH = "repro.control.dispatch"


def reduce(spans):
    """(ms per dispatch, note), or None without a dispatch."""
    dispatches = [s for s in spans if s.name == DISPATCH]
    if not dispatches:
        return None
    by_name = collections.defaultdict(lambda: {"count": 0, "s": 0.0})
    for s in spans:
        if s.own_compiles:
            by_name[s.name]["count"] += s.own_compiles
            by_name[s.name]["s"] += s.own_compile_s
    note = {"dispatches": len(dispatches),
            "compiles_by_span": dict(sorted(by_name.items()))}
    ms = 1e3 * sum(s.compile_s for s in dispatches) / len(dispatches)
    return ms, note


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    out = reduce(obs.spans().spans)
    if out is None:
        return None
    ctx.note("compile_ms_per_dispatch.control", out[1])
    return out[0]
