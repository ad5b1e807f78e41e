"""XLA program executions per query request, from the device trace: the
dispatch fan-out of the engine's query path (planner chunks, segments,
padding and cropping programs)."""

UNIT = "programs"


def read(ctx):
    from bench import tracefile

    if ctx.trace is None:
        return None
    spans = ctx.trace.request_spans("query")
    if not len(spans):
        return None
    return tracefile.programs_in(ctx.trace, spans) / len(spans)
