"""Router: microseconds of admission per routed request (the program's span
``repro.router.admit``: ``Router.submit`` from entry to return, over the
window's routed requests)."""

READS = "router_admit_s"


def read(ctx):
    if READS not in ctx.counters or not ctx.counters.get("router_routed"):
        return None
    return 1e6 * ctx.counters[READS] / ctx.counters["router_routed"]
