"""Where a served program's device time goes, by the scope its operations
were traced under: a builder's tool (no benchmark cell imports it).

A device trace names each operation by its HLO instruction
(`fusion.2165`), which says nothing of what it computes; the optimised HLO
of the same executable carries each instruction's `op_name`
(`jit(decode_multi)/while/body/closed_call/moe.experts/moe.plan/reduce_sum`:
the `jax.named_scope`s and inner jits it was traced under). Joining the two
by instruction name sums the trace per scope. The HLO has to be the served
executable's own (instruction numbers differ between the chip's compile and
a described-chip compile here): have XLA dump it while the agent compiles,
in the same call as the traced run and with a compile cache that does not
hold the program yet:

    XLA_FLAGS="--xla_dump_to=<dir> --xla_dump_hlo_as_text \\
        --xla_dump_hlo_module_re=.*(decode_multi|prefill_install).*" \\
      python3 chipbench/run.py --workload <cell> --seed <n> --seconds 50 --trace 1
    python3 benchmarks/scope_table.py .chipbench_work/<cell>/trace <dir> \\
        --program decode_multi --per-call 8

prints, per scope, the median over the traced executions of (ms, op
executions) / `--per-call` (a decode call's horizon), and the instructions
that took most time with their `op_name`. Of the HLO modules in `<dir>` it
takes the one that knows most of the trace's instruction names and says what
share it knows.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import statistics
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# First match wins; a custom call under a marker is that scope's kernel.
MARKERS = ("moe.plan", "jit(_moe_experts_impl)", "moe.route", "moe.experts",
           "mla.decode", "jit(_paged_attention_impl)",
           "jit(_ssm_update_impl)")


def instruction_scopes(hlo_text: str) -> dict:
    """{instruction name: (opcode, op_name)} of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        if not m:
            continue
        rest, depth = line[m.end():], 0
        if rest.startswith("("):            # a tuple type: skip to its end
            for i, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            rest = rest[i + 1:].lstrip()
        else:
            rest = rest.partition(" ")[2]
        opcode = re.match(r"([a-z][\w\-]*)\(", rest)
        if opcode:
            name = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = (opcode.group(1), name.group(1) if name else "")
    return out


def scope_of(opcode: str, op_name: str, markers=MARKERS) -> str:
    for marker in markers:
        if marker in op_name:
            return marker + (":kernel" if opcode == "custom-call" else "")
    return "-"


def table(ir: dict, scopes: dict, program: str, per_call: int,
          markers=MARKERS) -> dict:
    """`ir`: `chipbench.xplane.load`'s structure. Returns {"known": share of
    the op executions whose instruction `scopes` knows, "calls": n,
    "rows": {scope: (ms, op executions)} medians over the calls / per_call,
    "top": [(instruction, ms, scope, op_name)]}; a row "(call)" holds the
    executions' own duration."""
    from chipbench import xplane

    calls, known, seen = [], 0, 0
    by_instruction = collections.Counter()
    for plane in ir.values():
        ops, i = plane.get(xplane.OP_LINE, []), 0
        for mod in plane.get(xplane.MODULE_LINE, []):
            if xplane.program_name(mod["name"]) != program:
                continue
            a, b = mod["start"], mod["start"] + mod["dur"]
            while i < len(ops) and ops[i]["start"] < a:
                i += 1
            row = collections.defaultdict(lambda: [0.0, 0])
            while i < len(ops) and ops[i]["start"] < b:
                op = ops[i]
                i += 1
                if xplane.op_stem(op["name"]) in xplane.CONTAINERS:
                    continue
                seen += 1
                known += op["name"] in scopes
                scope = (scope_of(*scopes[op["name"]], markers)
                         if op["name"] in scopes else "?")
                row[scope][0] += op["dur"]
                row[scope][1] += 1
                by_instruction[op["name"]] += op["dur"]
            if row:
                row["(call)"] = [mod["dur"], 0]
                calls.append(row)
    rows = {s: (1e3 * statistics.median(c[s][0] if s in c else 0.0
                                        for c in calls) / per_call,
                statistics.median(c[s][1] if s in c else 0
                                  for c in calls) / per_call)
            for s in {s for c in calls for s in c}}
    top = [(name, 1e3 * dur / max(1, len(calls)) / per_call,
            scope_of(*scopes.get(name, ("?", "?")), markers),
            scopes.get(name, ("?", "?"))[1])
           for name, dur in by_instruction.most_common(25)]
    return {"known": known / max(1, seen), "calls": len(calls),
            "rows": rows, "top": top}


def main() -> int:
    from chipbench import xplane

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb or a directory holding one")
    ap.add_argument("hlo_dir", help="where XLA dumped the optimised HLO")
    ap.add_argument("--program", default="decode_multi")
    ap.add_argument("--per-call", type=int, default=1)
    args = ap.parse_args()
    path = Path(args.trace)
    ir = xplane.load(path if path.is_file() else xplane.find_xplane(path))
    best = None
    for f in sorted(Path(args.hlo_dir).glob("*after_optimizations.txt")):
        if args.program in f.name:
            t = table(ir, instruction_scopes(f.read_text(errors="replace")),
                      args.program, args.per_call)
            if best is None or t["known"] > best[1]["known"]:
                best = (f.name, t)
    if best is None:
        print(f"no dump of {args.program} under {args.hlo_dir}",
              file=sys.stderr)
        return 1
    name, t = best
    print(f"{args.program}: {t['calls']} calls, {name} knows "
          f"{100 * t['known']:.1f}% of the op executions")
    for scope, (ms, n) in sorted(t["rows"].items(), key=lambda r: -r[1][0]):
        print(f"  {scope:34s} {ms:9.4f} ms {n:8.1f} ops")
    for inst, ms, scope, op_name in t["top"]:
        print(f"    {inst:34s} {ms:8.4f} ms  {scope:24s} {op_name[-72:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
