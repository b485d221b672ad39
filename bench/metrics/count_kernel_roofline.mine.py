"""Count kernels: share of the roofline over the traced job. The work is the
algorithm's count passes (bench/roofline.py); the time is the device time of
the ops the trace names below."""

KERNELS = ("support_count_pallas", "support_count_packed_pallas")


def read(ctx):
    return ctx.roofline("count", KERNELS)
