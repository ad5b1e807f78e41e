"""Device time of one seal, in ms: the operations of the programs launched
under the program's ``repro.store.seal`` spans inside insert requests, over
the number of those spans."""

UNIT = "ms"


def read(ctx):
    from bench import attribution

    tr = ctx.trace
    if tr is None or not attribution.has_program_spans(tr):
        return None
    spans = tr.request_spans("insert")
    seals = [s for n, s, _, _ in tr.spans if n == "repro.store.seal"
             and any(lo <= s < hi for lo, hi in spans)]
    if not seals:
        return None
    return attribution.busy_ns_under(tr, "repro.store.seal", within=spans) / 1e6 / len(seals)
