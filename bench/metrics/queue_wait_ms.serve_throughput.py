"""Gateway: milliseconds a dispatched request waited in the queue, submit to
drain, on average (the program's ``queue_wait_s`` over the real rows of the
window's dispatches)."""

READS = "queue_wait_s"


def read(ctx):
    if READS not in ctx.counters or not ctx.counters.get("batch_rows_real"):
        return None
    return 1000.0 * ctx.counters[READS] / ctx.counters["batch_rows_real"]
