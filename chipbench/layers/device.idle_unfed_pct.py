"""Device: share of the traced window in which no operation ran on the chip
and the engine's pump was not inside `engine.fetch_wait` (hostspans.py):
idle time the host could have filled, as against idle time it spent
waiting for the chip's own results to arrive. At most `device.idle_pct`."""

from chipbench import hostspans


def read(ctx):
    spans = ctx.get("host_spans")
    if not ctx.get("trace") or not spans:
        return None
    unfed, window = hostspans.idle_unfed(ctx["trace"], spans)
    if window <= 0:
        return None
    return 100.0 * unfed / window
