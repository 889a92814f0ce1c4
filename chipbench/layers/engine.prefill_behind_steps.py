"""Engine loop: decode steps that were dispatched and not yet fetched when
an admission's `prefill_install` was dispatched, mean per admission over
the last 30 s of the window = prefill_behind_steps / admissions
(`/stats`.engine_trace.recent). It is what the chip runs before it reaches
an arrival's prefill: a whole looked-ahead call reads as its horizon, a
pump that dispatches at the seam between two calls as 0. A program without
the counter (before PR 29) reports nothing: a missing counter is not 0."""

from chipbench import engine_trace


def read(ctx):
    if "prefill_behind_steps" not in (engine_trace.recent(ctx) or {}):
        return None
    return engine_trace.ratio(ctx, "prefill_behind_steps", "admissions")
