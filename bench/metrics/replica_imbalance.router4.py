"""Router: the busiest replica's real batch rows over the mean replica's, in
percent over the window (the replicas' gateway counters; 100 is even)."""

READS = ("replica_rows_max", "replica_rows_mean")


def read(ctx):
    if any(k not in ctx.counters for k in READS) or not ctx.counters["replica_rows_mean"]:
        return None
    return 100.0 * ctx.counters["replica_rows_max"] / ctx.counters["replica_rows_mean"]
