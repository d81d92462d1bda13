"""device_idle_share: the share of the traced window in which no operation
ran on the device (1 minus the union of operation intervals over the
window, averaged over the cell's chips).  Moves ``tokens_per_s``.
"""


def read(ctx):
    return 100.0 * ctx.summary.idle_share
