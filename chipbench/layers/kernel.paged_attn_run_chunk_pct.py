"""Kernels: share of the page walk's chunks (stretches of 16 table
entries, the last of a row partial) that the decode kernel fetched with
one DMA per side, because the chunk was full and its pool pages adjacent,
up or down = 100 x walk_run_chunks / walk_chunks over the last 30 s of the
window (`/stats`.engine_trace.recent; counted on the host at each decode
dispatch by the kernel's own rule, `ops/page_walk.walk_run_counts`, over
the live rows x the call's steps). The other chunks cost two descriptors
and two waits a page. A program without the counter reports nothing."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.ratio(ctx, "walk_run_chunks", "walk_chunks", 100.0)
