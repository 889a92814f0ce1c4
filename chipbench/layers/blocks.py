"""What the `block.*` readers share: a traced run's device ops summed by the
block of the model they were traced under (no metric of its own: a helper,
loaded with `harness.load_file` as `kernel.ssm_update_state_bw_pct.py` loads
its neighbour; `benchmarks/scope_table.py` sums by finer scopes through it).

The program names every device op's block while it traces
(`xllm_service_tpu/models/base.block`: `jax.named_scope("blk.attn")`, ...), and
that name is part of the op's `op_name` in HLO metadata. A `jax.profiler`
session on a TPU writes it into the `.xplane.pb` itself: on the device plane,
each op's event METADATA (the record its events point to by id) carries the
stats `program_id` and, where the instruction has an `op_name`, `tf_op`
(`<op_name>:`). `jax.profiler.ProfileData` shows an event's own stats only,
so this file walks the protobuf's wire format for the device planes' event
metadata and nothing else (the lines, 60 of a trace's 70 MB, are skipped by
their length: ~0.1 s a trace), once a process (`op_names`, a memo by path).
The events themselves are the ones run.py has parsed (`ctx["trace"]`:
`xplane.load`'s name / start / duration); an event finds its op_name by the
program its execution names (`jit_decode_multi(<program_id>)`) and its
instruction's name.

A fusion is one instruction with one `op_name`, its root's: a residual add
fused into the next block's first product counts there. The blocks are exact
in their sum and approximate at their edges.

A trace whose ops name no block (a program without the scopes, or an
executable compiled before them) gives None from every reader, never 0.
"""

from __future__ import annotations

import re
import statistics
from pathlib import Path

from chipbench import harness, xplane

PREFIX = "blk."          # models/base.BLOCK_PREFIX: the parent has no such name
UNSCOPED = ""            # the block of an op the source knows, under none
PROGRAM_ID = re.compile(r"\((\d+)\)$")
PARSED: list = []        # every file `op_names` walked in this process
_NAMES: dict = {}
_CALLS: dict = {}


# ------------------------------------------------------------ the wire format
def _varint(buf, i: int):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited field or a fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield tag >> 3, wire, value


def _map_value(entry):
    """The value of one `map<int64, Message>` entry."""
    return next((v for f, w, v in _fields(entry) if f == 2 and w == 2), None)


def _plane_op_names(plane, out: dict) -> None:
    """XPlane: name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata: name = 2, stats = 5; XStat: metadata_id = 1,
    uint64 / int64 = 3 / 4, str = 5, ref (a stat_metadata id) = 7;
    XStatMetadata: id = 1, name = 2."""
    name, events, stat_names = "", [], {}
    for f, w, v in _fields(plane):
        if f == 2 and w == 2:
            name = bytes(v).decode()
        elif f == 4 and w == 2:
            events.append(v)
        elif f == 5 and w == 2:
            sid, sname = 0, ""
            for g, gw, gv in _fields(_map_value(v) or b""):
                if g == 1 and gw == 0:
                    sid = gv
                elif g == 2 and gw == 2:
                    sname = bytes(gv).decode()
            stat_names[sid] = sname
    if not xplane.DEVICE_PLANE.search(name):
        return
    for entry in events:
        line, program, op_name = "", None, ""
        for f, w, v in _fields(_map_value(entry) or b""):
            if f == 2 and w == 2:
                line = bytes(v).decode(errors="replace")
            elif f == 5 and w == 2:
                key, value = None, None
                for g, gw, gv in _fields(v):
                    if g == 1 and gw == 0:
                        key = stat_names.get(gv)
                    elif g in (3, 4) and gw == 0:
                        value = gv
                    elif g == 5 and gw == 2:
                        value = bytes(gv).decode(errors="replace")
                    elif g == 7 and gw == 0:
                        value = stat_names.get(gv, "")
                if key == "program_id":
                    program = value
                elif key == "tf_op":
                    op_name = str(value).rpartition(":")[0] or str(value)
        if program is not None:
            out.setdefault(str(program), {})[xplane.short_name(line)] = op_name


def op_names(path: Path) -> dict:
    """{program id: {instruction: op_name}} of every instruction the trace's
    device planes hold metadata for ("" where the instruction has no
    op_name: the source knows it, and it lies in no scope). Walked once a
    process and file."""
    key = str(Path(path).resolve())
    if key not in _NAMES:
        PARSED.append(key)
        out: dict = {}
        for f, w, plane in _fields(memoryview(Path(path).read_bytes())):
            if f == 1 and w == 2:
                _plane_op_names(plane, out)
        _NAMES[key] = out
    return _NAMES[key]


# ------------------------------------------------------------------ the sums
def block_of(op_name: str) -> str:
    """The outermost `blk.*` component of an op_name, or UNSCOPED."""
    for part in op_name.split("/"):
        if part.startswith(PREFIX):
            return part[len(PREFIX):]
    return UNSCOPED


def call_sums(trace: dict, names: dict, program: str,
              classify=block_of) -> list:
    """For every execution of a program whose name starts with `program`
    (`decode_multi`; `prefill_install` pools the buckets and `_sp`): {key:
    [seconds, op executions]} with key = `classify(op_name)` of each op
    event inside it, None for an instruction the source does not know;
    `xplane.CONTAINERS` skipped (their time is their bodies'), and under
    "(call)" the execution's own duration. An instruction is looked up under
    its execution's program id, then under any program of the same name
    (the profiler keeps ONE metadata record for two programs' identical
    instruction lines)."""
    def program_id(mod):
        found = PROGRAM_ID.search(mod["name"])
        return found.group(1) if found else ""

    by_name: dict = {}       # program name -> its programs' tables, merged
    for plane in trace.values():
        for mod in plane.get(xplane.MODULE_LINE, []):
            pname = xplane.program_name(mod["name"])
            if pname.startswith(program):
                by_name.setdefault(pname, {}).update(
                    names.get(program_id(mod), {}))
    out = []
    for plane in trace.values():
        ops, i = plane.get(xplane.OP_LINE, []), 0
        for mod in plane.get(xplane.MODULE_LINE, []):
            pname = xplane.program_name(mod["name"])
            if not pname.startswith(program):
                continue
            own = names.get(program_id(mod), {})
            a, b = mod["start"], mod["start"] + mod["dur"]
            while i < len(ops) and ops[i]["start"] < a:
                i += 1
            row: dict = {}
            while i < len(ops) and ops[i]["start"] < b:
                op = ops[i]
                i += 1
                if xplane.op_stem(op["name"]) in xplane.CONTAINERS:
                    continue
                op_name = own.get(op["name"])
                if op_name is None:
                    op_name = by_name[pname].get(op["name"])
                key = None if op_name is None else classify(op_name)
                cell = row.setdefault(key, [0.0, 0])
                cell[0] += op["dur"]
                cell[1] += 1
            if row:
                row["(call)"] = [mod["dur"], 0]
                out.append(row)
    return out


def median_ms(calls: list, key, per_call: float) -> float | None:
    """Median over `calls` of `key`'s seconds (0 where a call has none), in
    ms / `per_call`; None where no call has the key."""
    if not any(key in c for c in calls):
        return None
    return 1e3 * statistics.median(
        c[key][0] if key in c else 0.0 for c in calls) / per_call


def names_a_block(calls: list) -> bool:
    return any(k not in (None, UNSCOPED, "(call)") for c in calls for k in c)


# ------------------------------------------------------------------ a run's
def trace_file(ctx) -> Path | None:
    """The `.xplane.pb` of the run `ctx` is of, where run.py had the agent
    write it."""
    if not ctx.get("trace") or not ctx.get("cell"):
        return None
    try:
        return xplane.find_xplane(
            harness.ROOT / ".chipbench_work" / ctx["cell"] / "trace")
    except FileNotFoundError:
        return None


def run_calls(ctx, program: str) -> list | None:
    """`call_sums` by block of the run's trace, once a process, file and
    program; None without a trace, and where no op of the program's
    executions names a block."""
    path = trace_file(ctx)
    if path is None:
        return None
    key = (str(path.resolve()), program)
    if key not in _CALLS:
        calls = call_sums(ctx["trace"], op_names(path), program)
        _CALLS[key] = calls if names_a_block(calls) else None
    return _CALLS[key]


def block_ms(ctx, program: str, block: str, per_call: float):
    calls = run_calls(ctx, program)
    return None if calls is None else median_ms(calls, block, per_call)


def decode_block_ms(ctx, block: str):
    """ms a step of `block` in the traced `decode_multi` executions."""
    if not ctx.get("engine"):
        return None
    return block_ms(ctx, "decode_multi", block,
                    ctx["engine"]["decode_horizon"])


def known_ops_pct(ctx):
    calls = run_calls(ctx, "decode_multi")
    if calls is None:
        return None
    seen = sum(n for c in calls for k, (_, n) in c.items() if k != "(call)")
    known = sum(n for c in calls for k, (_, n) in c.items()
                if k not in (None, "(call)"))
    return 100.0 * known / seen if seen else None
