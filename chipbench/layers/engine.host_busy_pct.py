"""Engine loop: share of the pump thread's time in which it was neither
blocked on the chip's results nor asleep = 1 - (fetch_wait + idle) / sum
of the six phases, over the last 30 s of the window
(`/stats`.engine_trace.recent.host_s)."""

from chipbench import engine_trace


def read(ctx):
    phases = (engine_trace.recent(ctx) or {}).get("host_s") or {}
    total = sum(phases.values())
    if total <= 0:
        return None
    waiting = phases.get("fetch_wait", 0.0) + phases.get("idle", 0.0)
    return 100.0 * (1.0 - waiting / total)
