"""Programs: device time of one decode step = median duration of
`decode_multi` executions in the trace / the configured horizon (under load
nearly every call runs the full horizon; shorter ones only shorten the
median's left tail)."""

import statistics

from chipbench import xplane


def read(ctx):
    if not ctx.get("trace"):
        return None
    durs = xplane.module_durations(ctx["trace"]).get("decode_multi")
    if not durs:
        return None
    return statistics.median(durs) * 1000.0 / ctx["engine"]["decode_horizon"]
