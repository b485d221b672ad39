"""Rulebook layer: seconds per job from the mined itemsets to the compiled
rulebook, timed by the benchmark around the program's ``compile_rulebook``."""

READS = "rulebook_s"


def read(ctx):
    return ctx.values.get(READS)
