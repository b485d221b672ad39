"""Device: percent of the traced window in which no op ran on a chip, the
mean over the replicas' chips."""


def read(ctx):
    return ctx.idle_share()
