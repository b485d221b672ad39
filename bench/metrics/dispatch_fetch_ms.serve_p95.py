"""Match kernel: milliseconds per dispatch the worker waited for the answers
to reach the host (the program's span ``repro.gateway.fetch``, over the
window's dispatches)."""

READS = "dispatch_fetch_s"


def read(ctx):
    if READS not in ctx.counters or not ctx.counters.get("batches"):
        return None
    return 1000.0 * ctx.counters[READS] / ctx.counters["batches"]
