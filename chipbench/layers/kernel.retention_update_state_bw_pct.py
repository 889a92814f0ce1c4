"""Kernels: share of the chip's published HBM bandwidth that the
retention-update kernel reaches on the bytes it must move = sum over the traced
`decode_multi` executions of (live slots x steps x the family's bytes for one
live slot and step: `ctx["family"].bytes.retention_update_bytes`, each live
slot's state read once and written once) / the summed seconds of the
`_retention_update_impl` events inside those same executions / peak bytes/s.

ONE window for bytes and time, as `kernel.ssm_update_state_bw_pct` (the reader
beside this file, whose pairing this one uses) has it: the live slots and
steps of a call are in the name of the marker the engine leaves when the
call's result has landed (`engine.decode_live.<live>.<steps>`); an execution
without its marker is dropped, bytes and seconds alike. It errs the same way:
a slot that stops inside a call is counted for the call's remaining steps, so
the share reads HIGH by at most half a horizon in a request's steps (answers
of 32 to 512 tokens at a horizon of 8: a few per cent)."""

from pathlib import Path

from chipbench import harness, peaks, xplane

_ms = harness.load_file(
    Path(__file__).with_name("kernel.retention_update_ms.py"))
_ssm = harness.load_file(
    Path(__file__).with_name("kernel.ssm_update_state_bw_pct.py"))


def paired(trace: dict, spans: dict) -> list:
    """[(live, steps, kernel seconds)] of the `decode_multi` executions
    that have their marker: the neighbour's walk (its marker's pattern and
    its allowance for the two clocks) over this kernel's events."""
    marks = sorted((s["start"], int(m.group(1)), int(m.group(2)))
                   for line in spans.values() for s in line
                   for m in [_ssm.MARKER.match(s["name"])] if m)
    out = []
    for pname, plane in trace.items():
        ends = [m["start"] + m["dur"]
                for m in plane.get(xplane.MODULE_LINE, [])
                if xplane.program_name(m["name"]) == "decode_multi"]
        secs = xplane.ops_inside({pname: plane}, "decode_multi",
                                 _ms.is_kernel)
        i = 0
        for k, (end, kernel_s) in enumerate(zip(ends, secs)):
            while i < len(marks) and marks[i][0] < end - _ssm.SKEW_S:
                i += 1
            nxt = ends[k + 1] if k + 1 < len(ends) else float("inf")
            if i < len(marks) and marks[i][0] < nxt - _ssm.SKEW_S:
                out.append((marks[i][1], marks[i][2], kernel_s))
                i += 1
    return out


def read(ctx):
    family = ctx.get("family")
    if not ctx.get("trace") or not ctx.get("host_spans") or not family:
        return None
    per_step = getattr(family.bytes, "retention_update_bytes", None)
    if per_step is None:
        return None
    calls = [c for c in paired(ctx["trace"], ctx["host_spans"]) if c[2] > 0]
    seconds = sum(s for _, _, s in calls)
    if not seconds:
        return None
    need = sum(per_step(ctx["hf"], live) * steps for live, steps, _ in calls)
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / seconds
