"""Engine loop: share of the admitted prompts' tokens whose keys and values
the prefix cache supplied (block-aligned match after the trim that keeps
one suffix token) = prefix_hit_tokens / prompt_tokens over the last 30 s of
the window (`/stats`.engine_trace.recent)."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.ratio(ctx, "prefix_hit_tokens", "prompt_tokens", 100.0)
