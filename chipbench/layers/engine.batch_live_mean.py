"""Engine loop: sequences a decode step served, mean over the steps of the
last 30 s of the window = live_slot_steps / decode_steps
(`/stats`.engine_trace.recent). At a fixed offered load fewer is better:
by Little's law the mean is arrival rate x time in the batch."""

from chipbench import engine_trace


def read(ctx):
    return engine_trace.ratio(ctx, "live_slot_steps", "decode_steps")
