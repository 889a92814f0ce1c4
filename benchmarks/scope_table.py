"""Where a served program's device time goes, by the scope its operations
were traced under: a builder's tool (no benchmark cell imports it).

A device trace names each operation by its HLO instruction (`fusion.2165`),
which says nothing of what it computes; the instruction's `op_name`
(`jit(decode_multi)/while/body/closed_call/blk.moe/moe.experts/moe.plan/
reduce_sum`: the `jax.named_scope`s and inner jits it was traced under) does,
and a `jax.profiler` session on a TPU writes it into the trace itself. This
tool reads what the benchmark's `block.*` readers read, through their helper
(`chipbench/layers/blocks.py`: the op's event metadata, `tf_op`), and sums by a
finer list: the inner scopes first (`moe.plan`, `mla.decode`, a kernel's jit),
then the program's blocks (`models/base.BLOCKS`), so that a block's row is
what of it lies outside its inner scopes. Any traced run will do:

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds 50 --trace 1
    python3 benchmarks/scope_table.py .chipbench_work/<cell>/trace \\
        --program decode_multi --per-call 8

prints, per scope, the median over the traced executions of (ms, op
executions) / `--per-call` (a decode call's horizon), the share of the op
executions the trace knows, and the `op_name`s that took most time.
"""

from __future__ import annotations

import argparse
import collections
import os
import statistics
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xllm_service_tpu.models.base import BLOCK_PREFIX, BLOCKS  # noqa: E402

# First match wins: the inner scopes, then the blocks they nest in. A
# Pallas call under a marker is that scope's kernel.
INNER = ("moe.plan", "jit(_moe_experts_impl)", "jit(_paged_attention_impl)",
         "jit(_prefill_attention_impl)",
         "jit(_ssm_update_impl)", "jit(_retention_update_impl)",
         "jit(_retention_prefill_impl", "moe.route", "moe.experts",
         "mla.decode", "ssm_conv", "ssm_update", "ssm_scan", "ret.update",
         "ret.prefill")
MARKERS = INNER + tuple(BLOCK_PREFIX + b for b in BLOCKS)


def helper():
    """chipbench/layers/blocks.py, the one way to sum a trace by scope."""
    from chipbench import harness

    return harness.load_file(
        harness.HARNESS / "layers" / "blocks.py")


def scope_of(op_name: str, markers=MARKERS) -> str:
    for marker in markers:
        if marker in op_name:
            return marker + (":kernel" if op_name.endswith("pallas_call")
                             else "")
    return "-"


def table(ir: dict, names: dict, program: str, per_call: int,
          markers=MARKERS) -> dict:
    """`ir`: `chipbench.xplane.load`'s structure; `names`: the helper's
    `op_names` ({program id: {instruction: op_name}}). Returns {"known":
    share of the op executions whose instruction `names` knows, "calls": n,
    "rows": {scope: (ms, op executions)} medians over the calls / per_call
    ("?": the instructions it does not know), "top": [(op_name, ms a call /
    per_call, scope)]}; a row "(call)" holds the executions' own duration."""
    blocks = helper()
    calls = blocks.call_sums(ir, names, program,
                             lambda op_name: scope_of(op_name, markers))
    by_name = blocks.call_sums(ir, names, program, lambda op_name: op_name)
    seen = sum(n for c in calls for k, (_, n) in c.items() if k != "(call)")
    unknown = sum(c[None][1] for c in calls if None in c)
    rows = {("?" if s is None else s): (
        1e3 * statistics.median(c[s][0] if s in c else 0.0
                                for c in calls) / per_call,
        statistics.median(c[s][1] if s in c else 0 for c in calls) / per_call)
        for s in {s for c in calls for s in c}}
    total = collections.Counter()
    for c in by_name:
        for op_name, (seconds, _) in c.items():
            if op_name not in (None, "(call)"):
                total[op_name] += seconds
    top = [(op_name, 1e3 * dur / max(1, len(calls)) / per_call,
            scope_of(op_name, markers))
           for op_name, dur in total.most_common(25)]
    return {"known": (seen - unknown) / max(1, seen), "calls": len(calls),
            "rows": rows, "top": top}


def main() -> int:
    from chipbench import xplane

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb or a directory holding one")
    ap.add_argument("--program", default="decode_multi")
    ap.add_argument("--per-call", type=int, default=1)
    args = ap.parse_args()
    path = Path(args.trace)
    path = path if path.is_file() else xplane.find_xplane(path)
    t = table(xplane.load(path), helper().op_names(path), args.program,
              args.per_call)
    if not t["calls"]:
        print(f"no execution of {args.program} in {path}", file=sys.stderr)
        return 1
    print(f"{args.program}: {t['calls']} calls, the trace knows "
          f"{100 * t['known']:.1f}% of the op executions")
    for scope, (ms, n) in sorted(t["rows"].items(), key=lambda r: -r[1][0]):
        print(f"  {scope:34s} {ms:9.4f} ms {n:8.1f} ops")
    for op_name, ms, scope in t["top"]:
        print(f"    {ms:8.4f} ms  {scope:28s} {op_name[-90:] or '(no op_name)'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
