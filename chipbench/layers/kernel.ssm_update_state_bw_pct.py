"""Kernels: share of the chip's published HBM bandwidth that the state-update
kernel reaches on the bytes it must move = sum over the traced `decode_multi`
executions of (live slots x steps x the family's bytes for one live slot and
step: `ctx["family"].bytes.ssm_update_bytes`) / the summed seconds of the
`_ssm_update_impl` events inside those same executions / peak bytes/s.

ONE window for bytes and time: both come from the trace. The live slots and
steps of a call are in the name of the marker the engine leaves when the
call's result has landed (`engine.decode_live.<live>.<steps>`,
engine/telemetry.py `mark_decode_landed`, on the trace's host plane). A call's
marker is the first one at or after the execution's end (the fetch returns
after the program has ended) and before the next execution's end; an
execution without one (cut by the window's edge, or a program without the
markers) is dropped, bytes and seconds alike, so a missing marker cannot
raise the share. (Not `live_slot_steps` of `/stats`.recent: 30 s of counter
under 4 s of trace are two windows.)

Which count it has, and which way it errs: `live` is the sequences the call
was DISPATCHED for. A slot that stops inside a call (its stop token or its
budget, on the device) is counted for the call's remaining steps though the
kernel no longer moves its state: the share reads HIGH by at most half a
horizon in a request's steps, a few per cent at this traffic. It cannot pass
100 short of that: the kernel cannot move bytes faster than the chip does."""

import re
from pathlib import Path

from chipbench import harness, peaks, xplane

MARKER = re.compile(r"^decode_live\.(\d+)\.(\d+)$")
SKEW_S = 0.0005     # the host's clock against the device's, at most
_ms = harness.load_file(
    Path(__file__).with_name("kernel.ssm_update_ms.py"))


def paired(trace: dict, spans: dict) -> list:
    """[(live, steps, kernel seconds)] of the `decode_multi` executions
    that have their marker."""
    marks = sorted((s["start"], int(m.group(1)), int(m.group(2)))
                   for line in spans.values() for s in line
                   for m in [MARKER.match(s["name"])] if m)
    out = []
    for pname, plane in trace.items():
        execs = [m for m in plane.get(xplane.MODULE_LINE, [])
                 if xplane.program_name(m["name"]) == "decode_multi"]
        secs = xplane.ops_inside({pname: plane}, "decode_multi",
                                 _ms.is_kernel)
        ends = [m["start"] + m["dur"] for m in execs]
        i = 0
        for k, (end, kernel_s) in enumerate(zip(ends, secs)):
            while i < len(marks) and marks[i][0] < end - SKEW_S:
                i += 1
            nxt = ends[k + 1] if k + 1 < len(ends) else float("inf")
            if i < len(marks) and marks[i][0] < nxt - SKEW_S:
                out.append((marks[i][1], marks[i][2], kernel_s))
                i += 1
    return out


def read(ctx):
    family = ctx.get("family")
    if not ctx.get("trace") or not ctx.get("host_spans") or not family:
        return None
    per_step = getattr(family.bytes, "ssm_update_bytes", None)
    if per_step is None:
        return None
    calls = [c for c in paired(ctx["trace"], ctx["host_spans"]) if c[2] > 0]
    seconds = sum(s for _, _, s in calls)
    if not seconds:
        return None
    need = sum(per_step(ctx["hf"], live) * steps for live, steps, _ in calls)
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
