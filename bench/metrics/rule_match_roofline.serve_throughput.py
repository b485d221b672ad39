"""Match kernel: share of the roofline over the traced part of the window.
The work is the algorithm's rule matching of the real baskets dispatched then
(bench/roofline.py); the time is the device time of the ops named below."""

KERNELS = ("rule_match_pallas",)


def read(ctx):
    return ctx.roofline("rule_match", KERNELS)
