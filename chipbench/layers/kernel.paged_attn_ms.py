"""Kernels: device time of the Pallas paged-attention kernel per decode
step = summed duration of its events (the kernel's own name,
`_paged_attention_impl`, is the op's name in the trace) inside each
`decode_multi` execution / horizon, median over executions."""

import re
import statistics

from chipbench import xplane

KERNEL = re.compile(r"^_?paged_attention")


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
