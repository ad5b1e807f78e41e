"""What a seal adds to an insert request, in ms, on the host clock: the
median time of traced insert requests that sealed the head (the store's
count of sealed segments grew) less the median of those that did not."""

import statistics

UNIT = "ms"


def read(ctx):
    reqs = [r for r in ctx.traced if r["op"] == "insert"]
    sealed = [r["t1"] - r["t0"] for r in reqs if r["sealed"]]
    plain = [r["t1"] - r["t0"] for r in reqs if not r["sealed"]]
    if not sealed or not plain:
        return None
    return 1e3 * (statistics.median(sealed) - statistics.median(plain))
