"""Gateway: milliseconds per dispatch spent answering: cache puts, response
accounting, futures and their callbacks (the program's span
``repro.gateway.respond``, over the window's dispatches)."""

READS = "dispatch_respond_s"


def read(ctx):
    if READS not in ctx.counters or not ctx.counters.get("batches"):
        return None
    return 1000.0 * ctx.counters[READS] / ctx.counters["batches"]
