"""Kernels: device time of the Pallas prefill-attention kernel in one
prefill call = summed duration of its events (the kernel's own name,
`_prefill_attention_impl`, is the op's name in the trace) inside each
`prefill_install*` execution (all buckets pooled, as `prog.prefill_call_ms`
pools them), median over executions, ms a call. It tells the kernel apart
from the products, the rotary embedding and the page write that share
`block.prefill_attn_ms`. Nothing, never 0, where the trace holds no such
event: a program whose prefill attends through XLA."""

import re
import statistics

from chipbench import xplane

KERNEL = re.compile(r"^_?prefill_attention")


def is_kernel(event) -> bool:
    return bool(KERNEL.search(event["name"]))


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    programs = {xplane.program_name(m["name"]) for plane in trace.values()
                for m in plane.get(xplane.MODULE_LINE, [])}
    sums = [s for p in sorted(programs) if p.startswith("prefill_install")
            for s in xplane.ops_inside(trace, p, is_kernel) if s > 0]
    if not sums:
        return None
    return statistics.median(sums) * 1000.0
