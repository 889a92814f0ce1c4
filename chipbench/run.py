#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the coordination server, the master and the engine agent as three OS
processes (the agent, through agent_main.py, is the one that holds the
chip), warms up every shape the cell's traffic uses, offers the cell's
traffic open-loop at the cell's fixed rate over the master's HTTP port for
`--seconds`, stops the processes, and only then takes the chip itself to
compare a sample of what was served with the plain reference. The last line
of stdout is the result; its last key, and the last lines of stderr, hold
every number `correct` rests on beside its limit. Without a TPU the run fails; `--rehearse` (the
builder's flag, never the driver's) runs the same code on the CPU and says
`cpu` in its device line.

`--control` adds the control of "how correct is decided": the reference
computed with weights one precision below the configuration's, read in the
program's place. `--bench-file` lets a test point at another BENCHMARK.json.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench import harness, loadgen  # noqa: E402
from chipbench.harness import Failure, say  # noqa: E402

LOWER = {"bfloat16": "int8", "int8": "int4"}   # the precision just below


def check_sample(cell, window: list, seed: int) -> list:
    """The window's requests that are compared with the reference, chosen
    before the run because they are the ones that ask for log-probabilities
    where the cell compares those: the longest, and one drawn from the seed
    out of each of `check_requests - 1` equal stretches of the window, so
    that every seed spreads them over the window alike."""
    if not window:
        return []
    longest = max(window, key=lambda r: (len(r.prompt) + r.max_tokens,
                                         r.rid))
    rest = [r for r in window if r is not longest]
    n = min(max(0, cell.check_requests - 1), len(rest))
    rng = random.Random(f"check-{seed}")
    return [longest] + [rng.choice(rest[i * len(rest) // n:
                                        (i + 1) * len(rest) // n])
                        for i in range(n)]


def serve(cell, args, outdir: Path, workdir: Path, platform: str) -> dict:
    """Everything that happens while the children live."""
    reqs = loadgen.schedule(cell.mix, cell.rate, args.seconds, args.seed,
                            cell.hf["vocab_size"])
    fills = [r for r in reqs if r.phase == "fill"]
    timed = [r for r in reqs if r.phase != "fill"]
    sample = check_sample(cell, [r for r in timed if r.phase == "window"],
                          args.seed)
    for r in sample:
        r.logprobs = cell.check_logprobs
    ramp_s = float(cell.mix["ramp_s"])
    cluster = harness.Cluster(cell, args.seed, outdir, workdir, platform)
    cluster.start()
    try:
        cluster.wait_ready()
        t_ready = time.monotonic()
        harness.send_serially(cluster.base, harness.warmup_requests(cell))
        t_warm = time.monotonic()
        harness.send_serially(cluster.base, fills)
        t_fill = time.monotonic()
        marks: dict = {"stats_filled": cluster.get_json(
            cluster.agent_base + "/stats")}
        trace_dir = workdir / "trace"
        if trace_dir.exists():
            shutil.rmtree(trace_dir)

        def in_thread(fn, *a, **k):
            return asyncio.get_running_loop().run_in_executor(
                None, lambda: fn(*a, **k))

        def loop_cmd(*a, **k):
            return in_thread(cluster.command, *a, **k)

        async def mark(name):
            marks[name] = await loop_cmd("mark", "mark")

        async def window_end():
            await mark("end")
            marks["stats"] = await in_thread(
                cluster.get_json, cluster.agent_base + "/stats")
            marks["hotpath"] = await in_thread(
                cluster.get_json, cluster.base + "/admin/hotpath")

        events = [(0.0, lambda: mark("start")), (args.seconds, window_end)]
        if args.trace:
            at = max(0.0, min(args.seconds * 0.4,
                              args.seconds - harness.TRACE_S - 1.0))
            events += [
                (at, lambda: loop_cmd("trace_start", "trace_started",
                                      dir=str(trace_dir))),
                (at + min(harness.TRACE_S, args.seconds / 2),
                 lambda: loop_cmd("trace_stop", "trace_stopped",
                                  timeout=300))]
        t0 = time.monotonic() + ramp_s + 0.25
        recs = asyncio.run(harness.drive(
            cluster.base, timed, t0, events,
            t0 + args.seconds + harness.DRAIN_S))
        report = cluster.command("report", "report")
    finally:
        cluster.stop()
    picked = {r.rid for r in sample}
    return {"recs": recs, "t0": t0, "marks": marks, "report": report,
            "sample": [r for r in recs if r.req.rid in picked and r.ok],
            "device": cluster.device, "started": cluster.started,
            "trace_dir": trace_dir,
            "setup_parts": {"boot_s": t_ready - T_START,
                            "weights_s": cluster.started.get("weights_s"),
                            "engine_s": cluster.started.get("engine_s"),
                            "warmup_s": t_warm - t_ready,
                            "fill_s": t_fill - t_warm,
                            "ramp_s": t0 - t_fill}}


def served_logprobs(rec, k: int):
    """(ids [T, k], values [T, k]) of one record's streamed top-k
    log-probabilities; None where the stream did not carry k for every
    token."""
    if len(rec.lps) != rec.tokens or any(len(d) != k for d in rec.lps):
        return None
    ids = [[harness.text_tokens(t)[0] for t in d] for d in rec.lps]
    return ids, [list(d.values()) for d in rec.lps]


def measure(ref_logits, chosen, tops) -> dict:
    """The numbers compared, from the reference's logits at the served
    positions (one [T, V] array per request): each served token's gap below
    the reference's best and, where `tops` has (ids, values) per request,
    the errors of the served top-k log-probabilities."""
    import numpy as np

    from chipbench import reference

    g = np.concatenate([reference.gap(lg, c)
                        for lg, c in zip(ref_logits, chosen)])
    out = {"tokens": int(g.size), "gap_max": float(g.max()),
           "gap_mean": float(g.mean()), "flipped": int((g > 0).sum())}
    if tops is not None:
        if any(t is None for t in tops):
            out["lp_error"] = "a compared request came without its logprobs"
            return out
        e = np.concatenate([reference.logprob_errors(lg, *t).ravel()
                            for lg, t in zip(ref_logits, tops)])
        out.update(lp_values=int(e.size), lp_max=float(np.abs(e).max()),
                   lp_rms=float(np.sqrt(np.mean(e * e))))
    return out


def compare(cell, sample, seed: int, control: bool) -> dict:
    """Takes the chip (the children are gone): teacher-forces prompt +
    served tokens through the reference of the configuration's family and
    reads the served tokens, and their log-probabilities where the cell
    asked for them, against it."""
    from chipbench import reference as shared

    reference = cell.family.reference
    prompts = [r.req.prompt for r in sample]
    outputs = [harness.text_tokens(r.text) for r in sample]
    k = cell.check_logprobs
    tops = [served_logprobs(r, k) for r in sample] if k else None
    served = cell.engine["weights"]
    seqs, pos = shared.teacher_forced(prompts, outputs)
    # The mix's longest request and answer: the same shapes in every run.
    pad = dict(pad_len=loadgen.longest_total(cell.mix),
               pad_pos=cell.mix["output_tokens"]["max"])
    t = time.monotonic()
    ref = reference.logits_at(seed, cell.hf, served, seqs, pos, "", **pad)
    out = dict(measure(ref, outputs, tops), requests=len(sample),
               reference_s=time.monotonic() - t)
    if control:
        t = time.monotonic()
        low = reference.logits_at(seed, cell.hf, served, seqs, pos,
                                  LOWER[served], **pad)
        ctops = [shared.top_logprobs(lg, k) for lg in low] if k else None
        out["control"] = dict(
            measure(ref, [lg.argmax(-1) for lg in low], ctops),
            precision=LOWER[served], seconds=time.monotonic() - t)
    return out


def decide(limits: dict, failed: int, compiled: int, loaded: int,
           paths: dict, required: dict, blocks: list | None,
           cmp_) -> tuple[bool, dict]:
    """`correct`, and every number it rests on beside its limit: no failed
    request, nothing compiled or loaded inside the window, every op of the
    served decode program that the configuration names on the path it names
    (`paths`: what `decode_multi` took by op, `required`: op -> the prefix
    its path must have; by default the Pallas paged-attention kernel; with
    no op named, or an empty prefix, nothing is held and it is not correct),
    the mix's shared prefixes held by the prefix cache from the window's
    start to its end (`blocks`: [held, needed], None where nothing is
    shared), and each number of the comparison with the reference that the
    cell's file gives a limit."""
    checks = {"failed_requests": [failed, 0],
              "compilations_in_window": [compiled, 0],
              "executables_loaded_in_window": [loaded, 0]}
    ok = (failed == 0 and compiled == 0 and loaded == 0 and cmp_ is not None
          and bool(required))
    for op, prefix in required.items():
        checks["decode_multi." + op] = [paths.get(op, ""), prefix + "*"]
        ok = ok and bool(prefix) and paths.get(op, "").startswith(prefix)
    if blocks is not None:
        checks["prefix_blocks_held_min"] = blocks
        ok = ok and blocks[0] >= blocks[1]
    for k, limit in limits.items():
        got = None if cmp_ is None else cmp_.get(k)
        checks[k] = [got, limit]
        ok = ok and got is not None and got <= limit
    return bool(ok), checks


def prefix_blocks(cell, marks: dict) -> list | None:
    """[fewest blocks the agent's prefix cache held at the window's two
    ends, the blocks of the mix's shared prefixes]."""
    sp = cell.mix.get("shared_prefix")
    if not sp:
        return None
    need = sp["groups"] * (sp["tokens"] // cell.engine["hash_block_size"])
    return [min(marks[m].get("cached_blocks", 0)
                for m in ("stats_filled", "stats")), need]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--bench-file", default="BENCHMARK.json")
    args = ap.parse_args(argv)
    platform = "cpu" if args.rehearse else "tpu"
    if not args.rehearse and os.environ.get(
            "JAX_PLATFORMS", "").lower() == "cpu":
        raise Failure("JAX_PLATFORMS=cpu: a cell is measured on the TPU or "
                      "not at all (--rehearse is the CPU walk-through)")
    if importlib.util.find_spec("xllm_service_tpu") is None:
        raise Failure("the program (xllm_service_tpu) is not in this "
                      "checkout: there is nothing to measure")
    if args.rehearse:
        os.environ.update(JAX_PLATFORMS="cpu", XLLM_PALLAS_INTERPRET="1")
    bench, search, cell, outdir, workdir = harness.prepare(
        args.bench_file, args.workload)
    harness.LOG_FILE = outdir / f"run_s{args.seed}_t{args.trace}.jsonl"
    harness.LOG_FILE.unlink(missing_ok=True)
    say(phase="cell", name=cell.name, seed=args.seed, seconds=args.seconds,
        trace=args.trace, rate_per_s=cell.rate, platform=platform,
        compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"])

    s = serve(cell, args, outdir, workdir, platform)
    recs, t0, marks, report = s["recs"], s["t0"], s["marks"], s["report"]
    setup_s = t0 - T_START
    e2e = harness.end_to_end(recs, t0, args.seconds)
    harness.dump_records(
        recs, t0, outdir / f"records_s{args.seed}_t{args.trace}.json")
    say(phase="setup", setup_s=setup_s, **s["setup_parts"])
    say(phase="family", **{k: s["started"].get(k) for k in (
        "family", "weights_file", "engine_config")})
    say(phase="window", **{k: e2e[k] for k in (
        "attempted", "failed", "ttft_ms.mean", "ttft_ms.p50", "ttft_ms.p90",
        "tpot_ms.p50", "tpot_ms.p90", "gap_ms.p95", "gap_ms.p99",
        "gap_samples", "out_tok_per_s", "send_late_ms.p99", "errors")},
        sent_all=len(recs), ok_all=sum(r.ok for r in recs),
        backlog_q3=harness.backlog_mean(recs, t0 + args.seconds / 2,
                                        t0 + args.seconds * 0.75),
        backlog_q4=harness.backlog_mean(recs, t0 + args.seconds * 0.75,
                                        t0 + args.seconds))

    compiled = (marks["end"]["compilations"] - marks["start"]["compilations"])
    loaded = marks["end"]["cache_loads"] - marks["start"]["cache_loads"]
    paths = (marks["stats"].get("attention_paths") or [{}])[0]
    decode_paths = paths.get("decode_multi", {})
    say(phase="paths", decode_multi=decode_paths, required=cell.decode_paths,
        attention_paths=paths)
    if compiled:
        say(phase="built_in_window", programs=[
            b for b in marks["end"]["built"]
            if b[0] >= marks["start"]["t"]])

    device = dict(s["device"])
    device["memory_peak_bytes"] = report["memory_peak_bytes"]
    metrics: dict = {}
    breakdown = None
    ctx = {"trace": None, "host_spans": None, "agent_stats": marks["stats"],
           "hotpath": marks["hotpath"], "hf": cell.hf, "engine": cell.engine,
           "family": cell.family, "device": device, "cell": cell.name,
           "client": e2e}
    if args.trace:
        from chipbench import hostspans, xplane

        xp = xplane.find_xplane(s["trace_dir"])
        (outdir / "trace_inventory.json").write_text(
            json.dumps(xplane.inventory(xp), indent=1))
        planes = xplane.DEVICE_PLANE
        if platform == "cpu":
            import re

            planes = re.compile(r"^/host:CPU$")
        ir = xplane.load(xp, planes=planes,
                         lines=None if platform == "cpu" else
                         (xplane.MODULE_LINE, xplane.OP_LINE))
        (outdir / "trace_head.json").write_text(
            json.dumps(xplane.trim(ir, 0.12)))
        ctx["trace"], ctx["host_spans"] = ir, hostspans.load_spans(xp)
        busy, window = xplane.busy_and_window(ir)
        device["busy_s"], device["window_s"] = busy, window
        # The result's line may hold ten rows of each; the line before it
        # holds 25 operations, so that a family's kernels and whatever a
        # PR removes can be read and not only bounded by the tenth row.
        ops = xplane.top_ops(ir, 25)
        say(phase="device_ops", rows=ops)
        breakdown = {"device_ops": ops[:10],
                     "idle_gaps": hostspans.idle_by_span(
                         ir, ctx["host_spans"])[:10]}
        for m in harness.metrics_for(bench, "per_layer", cell.name):
            value = harness.load_reader(search, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in harness.metrics_for(bench, "end_to_end", cell.name):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    # The comparison with the plain reference, after the chip is free.
    sample = s["sample"]
    cmp_ = compare(cell, sample, args.seed, args.control) if sample else None
    say(phase="reference", **(cmp_ or {"error": "no finished request"}))
    ok, checks = decide(cell.limits, e2e["failed"], compiled, loaded,
                        decode_paths, cell.decode_paths,
                        prefix_blocks(cell, marks), cmp_)

    result = {"correct": bool(ok), "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # Each number compared beside its limit, last in the result's line and
    # last on stderr: of a run that is not correct the driver's record keeps
    # the ends of those two and nothing else.
    result["compared"] = checks
    say(**result)
    for name, (got, limit) in checks.items():
        print(f"chipbench: {name} {got} limit {limit}", file=sys.stderr)
    print(f"chipbench: correct {ok}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Failure as e:
        print(f"chipbench: {e}", file=sys.stderr)
        sys.exit(1)
