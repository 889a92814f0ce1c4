"""Engine loop: share of the seams between a running decode call and the
program behind it that the pump hid from the chip = hit / (hit + late +
skipped) of `look_ahead_late` over the last 30 s of the window
(`/stats`.engine_trace.recent; engine/telemetry.py, PR 42). `hit`: the next
program was on the queue before the running call ended; `late`: it was
dispatched late and the call had ended already; `skipped`: the pump fetched
first because its estimate's error passed the margin. A program without the
counter (before PR 42), or a window in which the pump met no such seam,
reports nothing: a share of nothing is not 0."""

from chipbench import engine_trace


def read(ctx):
    seams = (engine_trace.recent(ctx) or {}).get("look_ahead_late") or {}
    met = sum(seams.get(k, 0) for k in ("hit", "late", "skipped"))
    return 100.0 * seams.get("hit", 0) / met if met > 0 else None
