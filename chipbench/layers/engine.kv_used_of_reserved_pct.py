"""Engine loop: tokens the live sequences have written over the room their
pages reserve (admission reserves for `max_total_len` up front), mean over
the decode steps of the last 30 s of the window = context_token_steps /
(pages_reserved_steps x page_size) (`/stats`.engine_trace.recent)."""

from chipbench import engine_trace


def read(ctx):
    page = (ctx.get("engine") or {}).get("page_size")
    if not page:
        return None
    return engine_trace.ratio(ctx, "context_token_steps",
                              "pages_reserved_steps", 100.0 / page)
