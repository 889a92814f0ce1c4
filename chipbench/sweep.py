#!/usr/bin/env python3
"""The knee finder: one boot, a ladder of rates, the backlog rule.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1.5,2,2.5,3,3.5

Boots the cell's cluster once (the boot is most of a run's cost), offers the
cell's mix at each rate of the ladder for `--seconds`, lets the queue drain
between rates, and prints one row per rate and the knee: the highest rate
such that it and every lower rate of the ladder had no failed request and a
time to first token, in the window's second half, no more than twice the
lowest rate's (stats.knee). A cell's
fixed rate is 0.8 x knee, written into its file under cells/. A later
`benchmark` PR runs this again when an optimisation has moved the knee.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import harness, loadgen, stats  # noqa: E402
from chipbench.harness import Failure, say  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests/s, ascending")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench-file", default="BENCHMARK.json")
    args = ap.parse_args()
    platform = "cpu" if args.rehearse else "tpu"
    _, _, cell, outdir, workdir = harness.prepare(
        args.bench_file, args.workload, ".sweep")
    (outdir / "sweep.jsonl").unlink(missing_ok=True)
    rates = sorted(float(r) for r in args.rates.split(","))
    vocab = cell.hf["vocab_size"]

    cluster = harness.Cluster(cell, args.seed, outdir, workdir, platform)
    cluster.start()
    rows = []
    try:
        cluster.wait_ready()
        harness.send_serially(cluster.base, harness.warmup_requests(cell))
        filled = False
        for i, rate in enumerate(rates):
            reqs = loadgen.schedule(cell.mix, rate, args.seconds,
                                    args.seed + i, vocab)
            if not filled:
                # Prefixes are a function of the seed: one seed's for all
                # rates, filled once.
                prefixes = {r.group: r.prompt for r in reqs
                            if r.phase == "fill"}
                harness.send_serially(
                    cluster.base, [r for r in reqs if r.phase == "fill"])
                filled = True
            timed = [r for r in reqs if r.phase != "fill"]
            sp = cell.mix.get("shared_prefix")
            if sp and i:
                for r in timed:   # keep the filled prefixes, new private parts
                    r.prompt = (prefixes[r.group][:sp["tokens"]]
                                + r.prompt[sp["tokens"]:])
            c0 = cluster.command("mark", "mark")
            t0 = time.monotonic() + float(cell.mix["ramp_s"]) + 0.25
            recs = asyncio.run(harness.drive(
                cluster.base, timed, t0, [],
                t0 + args.seconds + harness.DRAIN_S))
            c1 = cluster.command("mark", "mark")
            e = harness.end_to_end(recs, t0, args.seconds)
            row = {"rate": rate, "attempted": e["attempted"],
                   "failed": e["failed"],
                   "backlog_q3": harness.backlog_mean(
                       recs, t0 + args.seconds / 2, t0 + args.seconds * 0.75),
                   "backlog_q4": harness.backlog_mean(
                       recs, t0 + args.seconds * 0.75, t0 + args.seconds),
                   "ttft_ms.p50_late": harness.late_ttft_p50(
                       recs, t0, args.seconds),
                   "ttft_ms.p50": e["ttft_ms.p50"],
                   "ttft_ms.p90": e["ttft_ms.p90"],
                   "tpot_ms.p50": e["tpot_ms.p50"],
                   "tpot_ms.p90": e["tpot_ms.p90"],
                   "gap_ms.p99": e["gap_ms.p99"],
                   "out_tok_per_s": e["out_tok_per_s"],
                   "offered_tok_per_s": sum(
                       r.req.max_tokens for r in recs
                       if r.req.phase == "window") / args.seconds,
                   "send_late_ms.p99": e["send_late_ms.p99"],
                   "compilations": c1["compilations"] - c0["compilations"],
                   "errors": e["errors"]}
            rows.append(row)
            say(phase="rate", **row)
            with open(outdir / "sweep.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
        report = cluster.command("report", "report")
    finally:
        cluster.stop()
    k = stats.knee(rows)
    say(phase="knee", knee_per_s=k,
        cell_rate_per_s=None if k is None else round(0.8 * k, 3),
        device=report["device"],
        memory_peak_bytes=report["memory_peak_bytes"])
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Failure as e:
        print(f"chipbench sweep: {e}", file=sys.stderr)
        sys.exit(1)
