"""Device idle time inside a query request while the program's own host code
runs, in ms per request: time inside both a query request and a ``repro.*``
span in which no operation ran on the device."""

UNIT = "ms"


def read(ctx):
    from bench import attribution

    return attribution.program_idle_ms_per_request(ctx, "query")
