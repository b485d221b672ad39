"""Level driver: seconds per job spent encoding candidate passes and placing
them on the device (the program's span ``repro.level.candidate_place``,
summed over the window's jobs)."""

READS = 'mine_phase_seconds{phase="candidate_place"}'


def read(ctx):
    if READS not in ctx.counters:
        return None
    return ctx.counters[READS] / ctx.values["jobs"]
