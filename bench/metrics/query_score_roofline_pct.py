"""Share of the scoring roofline reached inside query requests, in %.

The work of one request is the same whatever implements it: ``Q x C x N``
binary products of its ``Q`` real query rows with the ``C`` live corpus
rows at sketch width ``N``. A binary inner product is at best an int8
matrix product, so the operations are ``2 Q C N`` against the int8 peak;
the bytes are the packed corpus and queries, ``4 W (C + Q)``, and their
int32 fill counts, ``4 (C + Q)``, against HBM. The least time is the larger
of the two; the share is the least time of every traced query request over
the device's busy time inside those requests. Padded query rows and masked
corpus rows are not counted: they are waste, and the share shows it.
"""

UNIT = "%"


def work(q: int, c: int, n_bins: int, n_words: int):
    """(operations, bytes) of scoring ``q`` queries against ``c`` rows."""
    return 2 * q * c * n_bins, 4 * n_words * (c + q) + 4 * (c + q)


def least_s(q: int, c: int, n_bins: int, n_words: int, peaks: dict):
    """(least seconds, "ops" or "bytes": which bound applies)."""
    ops, nbytes = work(q, c, n_bins, n_words)
    t_ops, t_bytes = ops / peaks["int8_ops_s"], nbytes / peaks["hbm_bytes_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")


def read(ctx):
    from bench import tracefile

    if ctx.trace is None:
        return None
    reqs = [r for r in ctx.traced if r["op"] == "query"]
    spans = ctx.trace.request_spans("query")
    if not reqs or not len(spans):
        return None
    least, bounds = 0.0, set()
    for r in reqs:
        t, b = least_s(r["docs"], r["live"], ctx.cfg["n_bins"], ctx.cfg["n_words"], ctx.peaks)
        least += t
        bounds.add(b)
    busy = tracefile.busy_ns(ctx.trace, within=spans) / 1e9
    if busy <= 0:
        return None
    ctx.note(f"query_score_roofline_pct: {'/'.join(sorted(bounds))}-bound, least "
             f"{least / len(reqs) * 1e3:.4f} ms per request against "
             f"{busy / len(reqs) * 1e3:.4f} ms busy")
    return 100.0 * least / busy
