"""Host time of the per-tenant inputs per chunk of the traced window:
the self time (duration less child spans) of the program's
``repro.engine.tenants`` spans, where each (workload, fleet, seed)'s
tiled levels, tenant chips and jitter shifts are made, over the number
of ``repro.stream.dispatch`` spans (chunks).  A program without that
span reads nothing."""
import collections

SPAN = "repro.engine.tenants"
CHUNK = "repro.stream.dispatch"


def reduce(spans):
    chunks = sum(s.name == CHUNK for s in spans)
    mine = [s for s in spans if s.name == SPAN]
    if not chunks or not mine:
        return None
    children = collections.defaultdict(float)
    for s in spans:
        children[s.parent_id] += s.duration_s
    own = sum(s.duration_s - children[s.span_id] for s in mine)
    return 1e3 * own / chunks


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    return reduce(obs.spans().spans)
