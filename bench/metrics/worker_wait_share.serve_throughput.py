"""Gateway: percent of the dispatch worker's time spent waiting for work
(blocked on an empty queue, then collecting its batch) against its whole
loop: wait, launch, fetch and respond (the program's spans
``repro.batcher.wait`` and ``repro.gateway.*``, over the window)."""

READS = ("worker_wait_s", "dispatch_launch_s", "dispatch_fetch_s", "dispatch_respond_s")


def read(ctx):
    if any(k not in ctx.counters for k in READS):
        return None
    total = sum(ctx.counters[k] for k in READS)
    return 100.0 * ctx.counters["worker_wait_s"] / total if total > 0 else None
