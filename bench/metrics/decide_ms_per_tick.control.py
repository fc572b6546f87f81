"""The controller's decision per control tick, read from the program's
own spans (``repro.obs``) of the traced window: the summed duration of
the ``repro.controller.decide`` spans over the number of
``repro.control.tick`` spans.

The note gives each child of the tick (``repro.source.next``,
``repro.detector.step``, ``repro.controller.decide``,
``repro.control.dispatch``) in ms per tick, and the tick's self time.
A program without the span recorder reads nothing."""
import collections

TICK = "repro.control.tick"
DECIDE = "repro.controller.decide"


def reduce(spans):
    """(ms per tick, note), or None without ticks and decisions."""
    ticks = {s.span_id: s for s in spans if s.name == TICK}
    decide = [s for s in spans if s.name == DECIDE]
    if not ticks or not decide:
        return None
    n = len(ticks)
    child = collections.defaultdict(float)
    for s in spans:
        if s.parent_id in ticks:
            child[s.name] += s.duration_s
    tick_s = sum(t.duration_s for t in ticks.values())
    note = {"ticks": n,
            "child_ms_per_tick": {k: 1e3 * v / n
                                  for k, v in sorted(child.items())},
            "tick_self_ms": 1e3 * (tick_s - sum(child.values())) / n}
    return 1e3 * sum(s.duration_s for s in decide) / n, note


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    out = reduce(obs.spans().spans)
    if out is None:
        return None
    ctx.note("decide_ms_per_tick.control", out[1])
    return out[0]
