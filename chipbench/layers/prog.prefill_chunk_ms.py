"""Programs: device time of one chunk of a chunked prefill = median duration
of the `prefill_chunk` executions in the trace, ms. A long prompt is several
of these and one final `prefill_install` (`prog.prefill_call_ms` reads that
one alone); every decoding request waits behind each. A cell that serves
every prompt in one install runs no such program and the metric is left
out."""

import statistics

from chipbench import xplane


def read(ctx):
    if not ctx.get("trace"):
        return None
    durs = xplane.module_durations(ctx["trace"]).get("prefill_chunk")
    if not durs:
        return None
    return statistics.median(durs) * 1000.0
