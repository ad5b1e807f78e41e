"""Device busy time inside insert requests per document inserted, in
microseconds: what the write path (``count_update``, head bookkeeping,
seal) costs the device for each document."""

UNIT = "us/doc"


def read(ctx):
    from bench import tracefile

    if ctx.trace is None:
        return None
    spans = ctx.trace.request_spans("insert")
    docs = sum(r["docs"] for r in ctx.traced if r["op"] == "insert")
    if not len(spans) or docs == 0:
        return None
    return tracefile.busy_ns(ctx.trace, within=spans) / 1e3 / docs
