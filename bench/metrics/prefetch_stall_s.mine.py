"""Streaming layer: seconds per job the count loop waited for the next chunk
of the store (the program's mining counter, summed over the window's jobs)."""

READS = 'mine_phase_seconds{phase="prefetch_stall"}'


def read(ctx):
    if READS not in ctx.counters:
        return None
    return ctx.counters[READS] / ctx.values["jobs"]
