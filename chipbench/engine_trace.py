"""The engine loop's own counters as the agent's `/stats` gives them
(`engine_trace`, xllm_service_tpu/engine/telemetry.py): what the readers
under `layers/engine.*` share. They read `recent`, the counters' change
over the last 30 s before the snapshot run.py takes at the window's end:
seconds that lie wholly inside the window, where the totals since boot mix
in warm-up and ramp."""

from __future__ import annotations


def recent(ctx) -> dict | None:
    """`/stats`.engine_trace.recent, or None where the program has none."""
    trace = (ctx.get("agent_stats") or {}).get("engine_trace") or {}
    return trace.get("recent") or None


def ratio(ctx, num: str, den: str, scale: float = 1.0) -> float | None:
    """scale x recent[num] / recent[den]; None without the counters or
    with nothing counted."""
    r = recent(ctx)
    if not r or not r.get(den):
        return None
    return scale * r.get(num, 0) / r[den]
