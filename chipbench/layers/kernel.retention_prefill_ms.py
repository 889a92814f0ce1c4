"""Kernels: device time of the Pallas retention-prefill kernel per prefill
program = summed duration of its events (`_retention_prefill_impl_c<C>`: one
event a layer and sub-chunk of C tokens) inside each `prefill_install` or
`prefill_chunk` execution, chunks and installs pooled, median over
executions, ms. It is the work between sub-chunks (phi(Q) S, the normaliser,
the state's update); the masked (Q K^T)^2 inside a sub-chunk runs outside the
kernel, under the scope `ret.prefill` of `block.*`'s sums. A program without
the kernel (a parent commit, another family) has no such event and the metric
is left out."""

import re
import statistics

from chipbench import xplane

KERNEL = re.compile(r"^_?retention_prefill_impl_c(\d+)")
PROGRAMS = ("prefill_install", "prefill_chunk")


def is_kernel(event) -> bool:
    return bool(KERNEL.search(event["name"]))


def tokens_of(event) -> int:
    """The tokens one kernel event held: the C of its name."""
    return int(KERNEL.search(event["name"]).group(1))


def kernel_events(trace: dict):
    """(program, event) of every kernel event that ran inside an execution
    of one of PROGRAMS."""
    for plane in trace.values():
        mods = [(m["start"], m["start"] + m["dur"], prog)
                for m in plane.get(xplane.MODULE_LINE, [])
                for prog in [xplane.program_name(m["name"])]
                if prog in PROGRAMS]
        k = 0
        for ev in plane.get(xplane.OP_LINE, []):
            if not is_kernel(ev):
                continue
            while k < len(mods) and mods[k][1] <= ev["start"]:
                k += 1
            if k < len(mods) and mods[k][0] <= ev["start"]:
                yield mods[k][2], ev


def read(ctx):
    if not ctx.get("trace"):
        return None
    sums = [s for prog in PROGRAMS
            for s in xplane.ops_inside(ctx["trace"], prog, is_kernel)
            if s > 0]
    if not sums:
        return None
    return statistics.median(sums) * 1000.0
