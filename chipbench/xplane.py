"""From a profiler trace to numbers. The yardstick's part that no PR
claiming a gain can change.

`load()` turns the `.xplane.pb` that `jax.profiler` wrote into a plain
structure (planes -> lines -> events with start and duration in seconds);
everything else works on that structure, so the tests can feed it a small
recorded one.

On a TPU each chip is a plane `/device:TPU:<n>`. Its line `XLA Modules` has
one event per executed program (`jit_decode_multi(...)`), its line `XLA Ops`
one per HLO operation, and those are what "the device was busy" means here.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path, planes=DEVICE_PLANE, lines=(MODULE_LINE, OP_LINE)) -> dict:
    """{plane: {line: [{"name", "start", "dur"}]}} for the planes
    whose name matches `planes` and the lines named in `lines` (None = all)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out: dict = {}
    for plane in pd.planes:
        if not planes.search(plane.name):
            continue
        pl = out.setdefault(plane.name, {})
        for line in plane.lines:
            if lines is not None and line.name not in lines:
                continue
            evs = pl.setdefault(line.name, [])
            for ev in line.events:
                evs.append({"name": short_name(ev.name),
                            "start": ev.start_ns * 1e-9,
                            "dur": ev.duration_ns * 1e-9})
            evs.sort(key=lambda e: e["start"])
    return out


def inventory(path: Path, top: int = 12) -> dict:
    """What a trace holds, for a human deciding how to read it: every
    plane and line with its event count and its most frequent names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = {}
    for plane in pd.planes:
        pl = out.setdefault(plane.name, {})
        for line in plane.lines:
            names: dict = {}
            n, sample = 0, None
            for ev in line.events:
                n += 1
                names[ev.name] = names.get(ev.name, 0) + 1
                if sample is None:
                    sample = {k: (v if isinstance(v, (int, float)) else
                                  str(v)[:200]) for k, v in ev.stats}
            pl[line.name] = {
                "events": n, "first_event_stats": sample,
                "top_names": sorted(names.items(), key=lambda kv: -kv[1])[:top]}
    return out


def short_name(event_name: str) -> str:
    """An op's event name is its whole HLO line (`%fusion.3 = bf16[...]
    fusion(...)`); the instruction's own name is what identifies it."""
    return event_name.split(" = ")[0].lstrip("%")


CONTAINERS = {"while", "conditional", "call"}   # their time is their bodies'


def program_name(module_event_name: str) -> str:
    """`jit_decode_multi(1234)` -> `decode_multi`."""
    name = module_event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def op_stem(name: str) -> str:
    """`fusion.123` -> `fusion`; `%custom-call.4` -> `custom-call`."""
    return re.sub(r"[.\d]+$", "", name.lstrip("%")) or name


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_and_window(ir: dict) -> tuple[float, float]:
    """(busy seconds averaged over the chips in the trace, window seconds).
    Busy is the union of the op line's intervals; the window runs from the
    first to the last device event on any chip."""
    busy, lo, hi = [], None, None
    for plane in ir.values():
        ops = plane.get(OP_LINE) or plane.get(MODULE_LINE) or []
        iv = merge((e["start"], e["start"] + e["dur"]) for e in ops)
        busy.append(sum(b - a for a, b in iv))
        if iv:
            lo = iv[0][0] if lo is None else min(lo, iv[0][0])
            hi = iv[-1][1] if hi is None else max(hi, iv[-1][1])
    if not busy or lo is None:
        return 0.0, 0.0
    return sum(busy) / len(busy), hi - lo


def module_durations(ir: dict) -> dict[str, list[float]]:
    """{program: [seconds of each execution]} over all chips."""
    out: dict[str, list[float]] = {}
    for plane in ir.values():
        for e in plane.get(MODULE_LINE, []):
            out.setdefault(program_name(e["name"]), []).append(e["dur"])
    return out


def ops_inside(ir: dict, program: str, op_match) -> list[float]:
    """For every execution of `program`, the summed seconds of the ops that
    `op_match(event)` accepts and that ran inside it."""
    sums = []
    for plane in ir.values():
        ops = [e for e in plane.get(OP_LINE, []) if op_match(e)]
        i = 0
        for m in plane.get(MODULE_LINE, []):
            if program_name(m["name"]) != program:
                continue
            a, b = m["start"], m["start"] + m["dur"]
            while i < len(ops) and ops[i]["start"] < a:
                i += 1
            j, tot = i, 0.0
            while j < len(ops) and ops[j]["start"] < b:
                tot += ops[j]["dur"]
                j += 1
            i = j
            sums.append(tot)
    return sums


def top_ops(ir: dict, n: int = 10) -> list[list]:
    """[[program/op-stem, seconds]] of the device operations that took most
    time, attributed to the program that was running."""
    tot: dict[str, float] = {}
    for plane in ir.values():
        mods = plane.get(MODULE_LINE, [])
        k = 0
        for e in plane.get(OP_LINE, []):
            while k < len(mods) and mods[k]["start"] + mods[k]["dur"] <= e["start"]:
                k += 1
            prog = (program_name(mods[k]["name"])
                    if k < len(mods) and mods[k]["start"] <= e["start"]
                    else "-")
            label = op_stem(e["name"])
            if label in CONTAINERS:
                continue
            key = f"{prog}/{label}"
            tot[key] = tot.get(key, 0.0) + e["dur"]
    n_chips = max(1, len(ir))
    return [[k, v / n_chips] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def trim(ir: dict, seconds: float) -> dict:
    """The first `seconds` of a trace, for a fixture small enough to commit."""
    out = {}
    for pname, plane in ir.items():
        starts = [ev[0]["start"] for ev in plane.values() if ev]
        if not starts:
            continue
        t0 = min(starts)
        out[pname] = {ln: [e for e in evs if e["start"] < t0 + seconds]
                      for ln, evs in plane.items()}
    return out
