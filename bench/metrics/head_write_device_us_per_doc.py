"""Device time of the counting head's row writes per document inserted, in
microseconds: the operations of the programs launched under the program's
``repro.store.head_write`` span (the counter, packed-row, fill and clamp-flag
writes of ``_Head.append``), inside insert requests."""

UNIT = "us/doc"


def read(ctx):
    from bench import attribution

    return attribution.device_us_per_insert_doc(ctx, "repro.store.head_write")
