"""Host time of the streaming executor per chunk, read from the
program's own spans (``repro.obs``) of the traced window: the summed
duration of the ``repro.stream.prepare``, ``repro.stream.dispatch``,
``repro.stream.materialize`` and ``repro.study.fill_chunk`` spans, less
the ``repro.stream.pull`` spans inside them (the host waiting on the
device), over the number of ``repro.stream.dispatch`` spans (chunks).

The note gives each span name's self time per chunk (its duration less
its children's), the pulls' wait per chunk and the compiles the spans
saw.  A program without the span recorder reads nothing."""
import collections

HOST = ("repro.stream.prepare", "repro.stream.dispatch",
        "repro.stream.materialize", "repro.study.fill_chunk")
PULL = "repro.stream.pull"
CHUNK = "repro.stream.dispatch"


def _under(span, names, by_id) -> bool:
    """Whether an enclosing span of ``span`` is named in ``names``."""
    p = by_id.get(span.parent_id)
    while p is not None:
        if p.name in names:
            return True
        p = by_id.get(p.parent_id)
    return False


def reduce(spans):
    """(ms per chunk, note), or None without a chunk."""
    chunks = sum(s.name == CHUNK for s in spans)
    if not chunks:
        return None
    by_id = {s.span_id: s for s in spans}
    host = sum(s.duration_s for s in spans
               if s.name in HOST and not _under(s, HOST, by_id))
    wait = sum(s.duration_s for s in spans
               if s.name == PULL and _under(s, HOST, by_id))
    children = collections.defaultdict(float)
    for s in spans:
        children[s.parent_id] += s.duration_s
    self_ms = collections.defaultdict(float)
    for s in spans:
        self_ms[s.name] += 1e3 * (s.duration_s - children[s.span_id]) / chunks
    note = {"chunks": chunks,
            "self_ms_per_chunk": dict(sorted(self_ms.items())),
            "pull_ms_per_chunk": 1e3 * wait / chunks,
            "compiles": sum(s.own_compiles for s in spans),
            "compile_s": sum(s.own_compile_s for s in spans)}
    return 1e3 * (host - wait) / chunks, note


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    out = reduce(obs.spans().spans)
    if out is None:
        return None
    ctx.note("host_ms_per_chunk.sweep", out[1])
    return out[0]
