"""Kernels: share of the chip's published HBM bandwidth that the routed
experts' grouped products reach on the weights they must read = sum over
the traced `decode_multi` executions of (experts touched in the call x one
expert's bytes: `ctx["family"].bytes.moe_expert_bytes`) / the summed seconds
of the `_moe_experts_impl` events inside those same executions / peak
bytes/s.

ONE window for bytes and time: both come from the trace. The experts a call
touched (those that got at least one live row, summed over its steps and
expert layers, counted on the device by the router itself) are in the name
of the marker the engine leaves when the call's result has landed
(`engine.moe.landed.<touched>.<rows>.<steps>`, engine/telemetry.py
`moe_landed`, on the trace's host plane). A call's marker is the first one
at or after the execution's end (the fetch returns after the program has
ended) and before the next execution's end; an execution without one (cut by
the window's edge, or a program without the markers) is dropped, bytes and
seconds alike, so a missing marker cannot raise the share.

Which way it errs: an expert touched is read at least once, whole, by each
of its three products, and one whose rows straddle two row tiles may be
read twice by a column strip, so the bytes counted are a LOWER bound of
the bytes moved and the share cannot pass 100 unless the count is wrong. The
rows' own bytes (activations in and out) are left out: under 2% of an
expert's at these widths."""

import re
from pathlib import Path

from chipbench import harness, peaks, xplane

MARKER = re.compile(r"^moe\.landed\.(\d+)\.(\d+)\.(\d+)$")
SKEW_S = 0.0005     # the host's clock against the device's, at most
_ms = harness.load_file(
    Path(__file__).with_name("kernel.moe_experts_ms.py"))


def paired(trace: dict, spans: dict) -> list:
    """[(experts touched, kernel seconds)] of the `decode_multi`
    executions that have their marker."""
    marks = sorted((s["start"], int(m.group(1)))
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
                out.append((marks[i][1], kernel_s))
                i += 1
    return out


def read(ctx):
    family = ctx.get("family")
    if not ctx.get("trace") or not ctx.get("host_spans") or not family:
        return None
    per_expert = getattr(family.bytes, "moe_expert_bytes", None)
    if per_expert is None:
        return None
    calls = [c for c in paired(ctx["trace"], ctx["host_spans"]) if c[1] > 0]
    seconds = sum(s for _, s in calls)
    if not seconds:
        return None
    need = sum(t for t, _ in calls) * per_expert(
        ctx["hf"], ctx["engine"]["weights"])
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
