"""Level driver: seconds per job spent generating candidates on the host
(the program's mining counter, summed over the window's jobs)."""

READS = 'mine_phase_seconds{phase="candidate_gen"}'


def read(ctx):
    if READS not in ctx.counters:
        return None
    return ctx.counters[READS] / ctx.values["jobs"]
