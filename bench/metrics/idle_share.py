"""Device: share of the traced window in which no operation ran on the chip
(1 - union of device-op intervals / window, averaged over the chips used)."""


def read(ctx):
    return 100.0 * ctx["reduced"].idle_share
