"""Device: percent of the traced window in which no op ran on the chip."""


def read(ctx):
    return ctx.idle_share()
