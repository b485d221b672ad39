"""Gateway: real rows over the padded rows of every dispatched batch in the
window, in percent (the program's gateway counters)."""

READS = ("batch_rows_real", "batch_rows_padded")


def read(ctx):
    real, padded = (ctx.counters.get(k, 0) for k in READS)
    return 100.0 * real / padded if padded else None
