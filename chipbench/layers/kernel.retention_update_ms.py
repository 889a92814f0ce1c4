"""Kernels: device time of the Pallas retention-update kernel per decode step
= summed duration of its events (the kernel's own name,
`_retention_update_impl`, is the op's name in the trace; one event a layer and
step) inside each `decode_multi` execution / horizon, median over executions.
A program without the kernel (a parent commit, another family) has no such
event and the metric is left out."""

import re
import statistics

from chipbench import xplane

KERNEL = re.compile(r"^_?retention_update")


def is_kernel(event) -> bool:
    return bool(KERNEL.search(event["name"]))


def read(ctx):
    if not ctx.get("trace"):
        return None
    sums = [s for s in xplane.ops_inside(ctx["trace"], "decode_multi",
                                         is_kernel) if s > 0]
    if not sums:
        return None
    return statistics.median(sums) * 1000.0 / ctx["engine"]["decode_horizon"]
