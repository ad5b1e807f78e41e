"""Device time of counting per document inserted, in microseconds: the
operations of the programs launched under the program's ``repro.store.count``
span (deduplication and the ``count_update`` kernel), inside insert
requests."""

UNIT = "us/doc"


def read(ctx):
    from bench import attribution

    return attribution.device_us_per_insert_doc(ctx, "repro.store.count")
