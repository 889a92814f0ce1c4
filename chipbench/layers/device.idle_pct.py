"""Device: share of the traced window in which no operation ran on the chip
= 1 - union of busy intervals / window, averaged over the chips used."""

from chipbench import xplane


def read(ctx):
    if not ctx.get("trace"):
        return None
    busy, window = xplane.busy_and_window(ctx["trace"])
    if window <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
