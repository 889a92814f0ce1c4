"""Tracing-overhead micro-bench (ISSUE 3 acceptance: tracing-off <2%;
re-run in ISSUE 9 with federation + sampling in the tree).

Measures the fake-engine request path end-to-end (HTTP frontend ->
scheduler -> fake engine -> generations ingest -> response) under four
tracer configurations, against ONE shared cluster with the modes
interleaved round-robin (cluster-to-cluster and drift noise would
otherwise swamp the sub-ms effect being measured):

- ``off``     — tracing disabled: every span call is one attribute check +
                shared no-op singleton.
- ``ring``    — spans recorded into the in-memory SpanStore ring (default).
- ``sampled`` — ring at ``sample_rate=0.1`` with tail-based keep: ~90% of
                traces park in the pending buffer and are dropped at
                clean exit (the high-QPS always-on configuration).
- ``jsonl``   — ring + every finished span mirrored into a RequestTracer
                JSONL (the enable_request_trace pairing).

Also times the disabled `start_span` call in isolation (ns/call), and —
fleet observability plane — the cost of one `/admin/trace?scope=fleet`
assembly and one `/metrics/fleet` scrape against the live cluster
(query-side cost; the request path is untouched by federation).

Prints one JSON line per mode plus p50 overhead ratios vs ``off``, and a
BENCH_tracing-shaped document at the end (headline tracked by
scripts/bench_trend.py). Results are quoted in docs/observability.md and
docs/performance.md.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import json
import statistics
import tempfile
import time

import requests

MODES = ("off", "ring", "sampled", "jsonl")


def disabled_span_call_ns(iters: int = 200_000) -> float:
    from xllm_service_tpu.common.tracing import Tracer

    tr = Tracer()
    tr.configure(enabled=False)
    t0 = time.perf_counter()
    for _ in range(iters):
        sp = tr.start_span("frontend.request")
        sp.end()
    return (time.perf_counter() - t0) / iters * 1e9


def main() -> None:
    from xllm_service_tpu.common.config import ServiceOptions
    from xllm_service_tpu.common.tracing import TRACER
    from xllm_service_tpu.coordination.memory import (
        InMemoryCoordination,
        MemoryStore,
    )
    from xllm_service_tpu.http_service.request_tracer import RequestTracer
    from xllm_service_tpu.master import Master
    from xllm_service_tpu.testing.fake_engine import (
        FakeEngine,
        FakeEngineConfig,
    )

    print(json.dumps({"disabled_span_call_ns":
                      round(disabled_span_call_ns(), 1)}))

    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=2.0, sync_interval_s=1.0)
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()
    # Single-delta replies: the per-request fixed path (accept -> schedule
    # -> forward -> generate -> ingest -> respond) is what tracing
    # instruments; multi-delta streaming only adds thread-scheduling noise.
    engine = FakeEngine(
        InMemoryCoordination(store),
        FakeEngineConfig(reply_text="x" * 8, chunk_size=8,
                         delay_s=0.0)).start()
    deadline = time.time() + 10
    while not master.scheduler.has_available_instances():
        if time.time() > deadline:
            raise RuntimeError("fake engine never became available")
        time.sleep(0.05)

    jsonl_tracer = RequestTracer(tempfile.mkdtemp(prefix="bench-trace-"),
                                 enabled=True)

    def mirror(span: dict) -> None:
        jsonl_tracer.log(span.get("request_id", ""),
                         {"type": "span", "span": span})

    def set_mode(mode: str) -> None:
        TRACER.configure(enabled=mode != "off",
                         mirror=mirror if mode == "jsonl" else None,
                         sample_rate=0.1 if mode == "sampled" else 1.0)

    url = f"http://127.0.0.1:{master.http_port}/v1/completions"
    body = {"model": "fake-model", "prompt": "bench", "max_tokens": 8}
    session = requests.Session()

    def one() -> float:
        t0 = time.perf_counter()
        r = session.post(url, json=body, timeout=30)
        assert r.status_code == 200, r.text
        return (time.perf_counter() - t0) * 1000.0

    for _ in range(50):   # warmup (threads, sockets, code paths)
        one()

    ROUNDS, PER_ROUND = 12, 40
    lat: dict[str, list[float]] = {m: [] for m in MODES}
    for _ in range(ROUNDS):
        for mode in MODES:
            set_mode(mode)
            lat[mode].extend(one() for _ in range(PER_ROUND))
    set_mode("ring")

    results = {}
    for mode in MODES:
        xs = sorted(lat[mode])
        results[mode] = {
            "mode": mode,
            "n": len(xs),
            "mean_ms": round(statistics.fmean(xs), 3),
            "p50_ms": round(xs[len(xs) // 2], 3),
            "p95_ms": round(xs[int(len(xs) * 0.95)], 3),
        }
        print(json.dumps(results[mode]))
    base = results["off"]["p50_ms"]
    overheads = {}
    for mode in ("ring", "sampled", "jsonl"):
        ratio = (results[mode]["p50_ms"] - base) / base * 100.0
        overheads[mode] = round(ratio, 2)
        print(json.dumps({"overhead_vs_off": mode, "p50_pct": ratio}))

    # Fleet-endpoint query cost (not on the request path; informational).
    recent = session.get(
        f"http://127.0.0.1:{master.http_port}/admin/trace/recent",
        timeout=10).json()
    sid = recent["traces"][0]["request_id"] if recent["traces"] else ""
    fleet = {}
    for name, path, params in (
            ("fleet_trace_ms", "/admin/trace",
             {"scope": "fleet", "request_id": sid}),
            ("fleet_metrics_ms", "/metrics/fleet", {})):
        t0 = time.perf_counter()
        session.get(f"http://127.0.0.1:{master.http_port}{path}",
                    params=params, timeout=10)
        fleet[name] = round((time.perf_counter() - t0) * 1000.0, 3)
    print(json.dumps(fleet))

    doc = {
        "bench": "benchmarks/bench_tracing_overhead.py",
        "modes": results,
        "fleet_endpoint_cost": fleet,
        # Signed: negative = measured faster than off (noise); the
        # bench-trend tripwire judges *_pct headlines in absolute
        # points, so a clamped 0 would hide a later real regression.
        "headline": {
            "ring_overhead_p50_pct": overheads["ring"],
            "sampled_overhead_p50_pct": overheads["sampled"],
        },
    }
    print("BENCH_DOC " + json.dumps(doc))

    jsonl_tracer.close()
    engine.stop()
    master.stop()


if __name__ == "__main__":
    main()
