"""Streaming layer: seconds per job the chunk producer's thread spent placing
chunks on the device (the program's span ``repro.stream.chunk_place``,
summed over the window's jobs)."""

READS = 'mine_phase_seconds{phase="chunk_place"}'


def read(ctx):
    if READS not in ctx.counters:
        return None
    return ctx.counters[READS] / ctx.values["jobs"]
