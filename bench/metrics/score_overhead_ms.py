"""What scoring costs the device beyond the scoring kernel, per query request,
in ms: the operations of the programs launched under the program's
``repro.query.kernel_score`` span (relayout, padding, crops) less those of
the ``topk_stream`` kernel itself."""

UNIT = "ms"

KERNEL = "topk_stream"


def read(ctx):
    from bench import attribution

    tr = ctx.trace
    if tr is None or not attribution.has_program_spans(tr):
        return None
    spans = tr.request_spans("query")
    if not len(spans):
        return None
    stage = attribution.busy_ns_under(tr, "repro.query.kernel_score", within=spans)
    kernel = attribution.busy_ns_under(tr, "repro.query.kernel_score", within=spans,
                                       op=KERNEL)
    if kernel <= 0:
        return None
    n = len(spans)
    ctx.note(f"score_overhead_ms: repro.query.kernel_score {stage / 1e6 / n:.4f} ms of "
             f"device time per request, {KERNEL} {kernel / 1e6 / n:.4f} ms of it")
    return (stage - kernel) / 1e6 / n
