"""Gateway: milliseconds per dispatch from draining a batch to its match and
top-k launched: assembly, the baskets' transfer and the dispatch (the
program's span ``repro.gateway.launch``, over the window's dispatches)."""

READS = "dispatch_launch_s"


def read(ctx):
    if READS not in ctx.counters or not ctx.counters.get("batches"):
        return None
    return 1000.0 * ctx.counters[READS] / ctx.counters["batches"]
