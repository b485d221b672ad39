"""Streaming layer: seconds per job the chunk producer spent unpacking chunks
from packed words to dense int8 (the program's span
``repro.stream.chunk_unpack``, summed over the window's jobs)."""

READS = 'mine_phase_seconds{phase="chunk_unpack"}'


def read(ctx):
    if READS not in ctx.counters:
        return None
    return ctx.counters[READS] / ctx.values["jobs"]
