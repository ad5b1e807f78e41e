"""Share of the traced window in which no operation ran on the device, in
%, in an ingest cell: 1 - (union of device operation intervals / window)."""

UNIT = "%"


def read(ctx):
    from bench import tracefile

    if ctx.trace is None or not len(ctx.trace.request_spans("insert")):
        return None
    return tracefile.idle_share_pct(ctx.trace)
