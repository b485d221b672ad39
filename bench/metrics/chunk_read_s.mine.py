"""Store layer: seconds per job the chunk producer spent reading rows out of
the store's mmap, concatenating and padding them (the program's span
``repro.stream.chunk_read``, summed over the window's jobs)."""

READS = 'mine_phase_seconds{phase="chunk_read"}'


def read(ctx):
    if READS not in ctx.counters:
        return None
    return ctx.counters[READS] / ctx.values["jobs"]
