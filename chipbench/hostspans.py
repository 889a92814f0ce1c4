"""What the host was doing while the chip was idle.

The engine's pump thread wraps the phases of one loop iteration in
`jax.profiler.TraceAnnotation("engine.<phase>")` (admit, prefill_dispatch,
decode_dispatch, fetch_wait, emit, idle: xllm_service_tpu/engine/
telemetry.py), so a profiler session writes them into the trace's host
plane, on the clock of the `/device:TPU:<n>` planes. This file reads them
from the raw `.xplane.pb` (run.py hands them to the readers as
`ctx["host_spans"]`, beside the device planes in `ctx["trace"]`) and lays
them over the device's idle time:

  idle_by_span()  every gap between consecutive `XLA Modules` events,
                  charged to the engine phase that covers most of it
                  (`unnamed` where none covers any of it)
  idle_unfed()    the idle seconds during which the pump was NOT inside
                  `engine.fetch_wait`: the chip had nothing queued and the
                  host was not even waiting for it

A program without the annotations (a parent commit) gives no spans; the
readers built on this then return nothing.
"""

from __future__ import annotations

import re
from pathlib import Path

from chipbench import xplane

HOST_PLANE = re.compile(r"^/host:CPU$")
PREFIX = "engine."


def load_spans(path: Path) -> dict[str, list[dict]]:
    """{host thread: [{"name", "start", "dur"}]} of the `engine.*` events,
    names without the prefix, seconds on the trace's clock."""
    from jax.profiler import ProfileData

    out: dict[str, list[dict]] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not HOST_PLANE.search(plane.name):
            continue
        for n, line in enumerate(plane.lines):
            evs = [{"name": ev.name[len(PREFIX):], "start": ev.start_ns * 1e-9,
                    "dur": ev.duration_ns * 1e-9}
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                out[f"{line.name}#{n}"] = sorted(
                    evs, key=lambda e: (e["start"], -e["dur"]))
    return out


def exclusive(spans: list[dict]) -> list[tuple[float, float, str]]:
    """One thread's nested spans (sorted by start, outer first) as
    (start, end, name) pieces in which `name` is the innermost span
    running: a `fetch_wait` inside an `emit` takes its time out of it."""
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []     # (end, name) of open spans
    t = 0.0

    def close_until(limit: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for s in spans:
        close_until(s["start"])
        if stack and s["start"] > t:
            out.append((t, s["start"], stack[-1][1]))
        t = max(t, s["start"])
        stack.append((s["start"] + s["dur"], s["name"]))
    close_until(float("inf"))
    return out


def _pieces(spans: dict[str, list[dict]]) -> list[tuple[float, float, str]]:
    return sorted(p for line in spans.values() for p in exclusive(line))


def _overlaps(pieces, a: float, b: float) -> dict[str, float]:
    """Seconds of [a, b] under each span name."""
    got: dict[str, float] = {}
    for s, e, name in pieces:
        if s >= b:
            break
        if e > a:
            got[name] = got.get(name, 0.0) + min(e, b) - max(s, a)
    return got


def idle_by_span(ir: dict, spans: dict[str, list[dict]]) -> list[list]:
    """[[phase, seconds]]: idle time between consecutive device programs,
    summed by the engine phase covering most of each gap (`unnamed` where
    the trace holds no span there: a program without the annotations gives
    one `unnamed` row), averaged over the chips in the trace."""
    pieces = _pieces(spans)
    tot: dict[str, float] = {}
    for plane in ir.values():
        mods = plane.get(xplane.MODULE_LINE, [])
        for m, nxt in zip(mods, mods[1:]):
            a, b = m["start"] + m["dur"], nxt["start"]
            if b <= a:
                continue
            over = _overlaps(pieces, a, b)
            name = max(over, key=over.get) if over else "unnamed"
            tot[name] = tot.get(name, 0.0) + b - a
    n_chips = max(1, len(ir))
    return [[k, v / n_chips] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])]


def _shared(xs: list, ys: list) -> float:
    """Seconds two sorted lists of disjoint (start, end) have in common."""
    i = j = 0
    tot = 0.0
    while i < len(xs) and j < len(ys):
        tot += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_unfed(ir: dict, spans: dict[str, list[dict]]) -> tuple[float, float]:
    """(idle seconds outside `engine.fetch_wait`, window seconds): idle as
    `xplane.busy_and_window` takes it (no operation on the chip), minus the
    part of it the pump spent waiting for the chip's results; averaged over
    the chips in the trace."""
    waits = xplane.merge((s, e) for s, e, name in _pieces(spans)
                         if name == "fetch_wait")
    _, window = xplane.busy_and_window(ir)
    unfed = []
    for plane in ir.values():
        ops = plane.get(xplane.OP_LINE) or plane.get(xplane.MODULE_LINE) or []
        busy = xplane.merge((e["start"], e["start"] + e["dur"]) for e in ops)
        idle = [(a, b) for (_, a), (b, _) in zip(busy, busy[1:])]
        unfed.append(sum(b - a for a, b in idle) - _shared(idle, waits))
    return (sum(unfed) / len(unfed) if unfed else 0.0), window
