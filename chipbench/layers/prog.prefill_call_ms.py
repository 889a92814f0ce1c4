"""Programs: device time of one prefill call = median duration of
`prefill_install*` executions in the trace (all buckets pooled: what a
decoding request waits behind)."""

import statistics

from chipbench import xplane


def read(ctx):
    if not ctx.get("trace"):
        return None
    durs = [d for name, ds in xplane.module_durations(ctx["trace"]).items()
            if name.startswith("prefill_install") for d in ds]
    if not durs:
        return None
    return statistics.median(durs) * 1000.0
