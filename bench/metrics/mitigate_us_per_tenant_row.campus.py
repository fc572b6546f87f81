"""Device time of the mitigations (``_mitigate_vmapped``) per tenant row
of the traced window: a campus row of K tenants counts K.  The tenant
rows are the ``tenant_rows`` of the program's ``repro.stream.dispatch``
spans (``repro.obs``); a program whose spans do not carry them reads
nothing."""

PROGRAM = "_mitigate_vmapped"


def tenant_rows(spans):
    """The summed ``tenant_rows`` of the chunks dispatched, or None where
    a dispatch span lacks them."""
    rows = [s.attrs.get("tenant_rows") for s in spans
            if s.name == "repro.stream.dispatch"]
    if not rows or None in rows or not sum(rows):
        return None
    return sum(rows)


def reduce(program_s, spans):
    n = tenant_rows(spans)
    return None if not program_s or n is None else 1e6 * program_s / n


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    return reduce(ctx.program_s(PROGRAM), obs.spans().spans)
