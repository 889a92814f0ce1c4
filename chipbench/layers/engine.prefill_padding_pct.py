"""Engine loop: share of the token rows the chip computed in prefill that were
bucket padding = prefill_padded_tokens / (prefill_padded_tokens +
prompt_tokens - prefix_hit_tokens) over the last 30 s of the window
(`/stats`.engine_trace.recent). An admission's suffix (its prompt less what
the prefix cache served) is padded up to its `prefill_install` bucket; the
counter (`prefill_padded_tokens`, bucket - suffix, engine/telemetry.py) has
stood since PR 24. A program without it reports nothing: a missing counter
is not 0."""

from chipbench import engine_trace


def read(ctx):
    r = engine_trace.recent(ctx) or {}
    if "prefill_padded_tokens" not in r:
        return None
    padded = r["prefill_padded_tokens"]
    rows = padded + r.get("prompt_tokens", 0) - r.get("prefix_hit_tokens", 0)
    return 100.0 * padded / rows if rows > 0 else None
