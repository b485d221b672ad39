"""Router: the driver's sweeps and the health monitor's ticks, in percent of
the window (the program's spans ``repro.router.sweep`` and
``repro.router.health``; two threads, so the sum of both shares)."""

READS = ("router_sweep_s", "router_health_s")


def read(ctx):
    if any(k not in ctx.counters for k in READS) or not ctx.counters.get("window_s"):
        return None
    return 100.0 * sum(ctx.counters[k] for k in READS) / ctx.counters["window_s"]
