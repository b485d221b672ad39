"""Router: microseconds of completion per completed request (the program's
span ``repro.router.complete``: an attempt's answer through the outer future
and its callbacks, over the window's completed requests)."""

READS = "router_complete_s"


def read(ctx):
    if READS not in ctx.counters or not ctx.counters.get("router_completed"):
        return None
    return 1e6 * ctx.counters[READS] / ctx.counters["router_completed"]
