"""Generations-ingest micro-bench: deltas/sec the service plane sustains
on /rpc/generations (HTTP parse + scheduler dispatch + SSE fan-out), for
msgpack vs JSON framing. This is the hop that bounds aggregate decode
throughput across the fleet (reference ships batched protobuf here,
`rpc_service/service.cpp:149-215`).

Prints one JSON line per framing and the ratio.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import json
import time
import uuid

import msgpack
import requests


def main() -> None:
    from xllm_service_tpu.common.call_data import CollectingConnection
    from xllm_service_tpu.common.config import ServiceOptions
    from xllm_service_tpu.common.request import Request
    from xllm_service_tpu.common.types import InstanceType
    from xllm_service_tpu.coordination.memory import (
        InMemoryCoordination,
        MemoryStore,
    )
    from xllm_service_tpu.master import Master
    from xllm_service_tpu.testing.fake_engine import (
        FakeEngine,
        FakeEngineConfig,
    )

    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=2.0, sync_interval_s=1.0)
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()
    engine = FakeEngine(
        InMemoryCoordination(store),
        FakeEngineConfig(instance_type=InstanceType.MIX)).start()
    deadline = time.time() + 10
    while not master.scheduler.has_available_instances():
        if time.time() > deadline:
            raise RuntimeError("fake engine never became available")
        time.sleep(0.05)

    # In-flight streaming requests for the deltas to land on.
    N_REQ = 64
    sids = []
    for i in range(N_REQ):
        sid = f"bench-{uuid.uuid4().hex[:8]}"
        req = Request(service_request_id=sid, request_id=sid, model="fake",
                      stream=True, prompt="x", token_ids=[1, 2, 3])
        assert master.scheduler.schedule(req).ok()
        master.scheduler.record_new_request(
            req, CollectingConnection(stream=True), "completion")
        sids.append(sid)

    url = f"http://127.0.0.1:{master.rpc_port}/rpc/generations"
    BATCH = 32        # deltas per POST (the agent's flush batching)
    ROUNDS = 60
    results = {}
    for mode in ("json", "msgpack"):
        seq = {sid: 0 for sid in sids}
        t0 = time.perf_counter()
        n = 0
        for r in range(ROUNDS):
            gens = []
            for k in range(BATCH):
                sid = sids[(r * BATCH + k) % N_REQ]
                seq[sid] += 1
                gens.append({
                    "request_id": sid, "service_request_id": sid,
                    "status": {"code": 0, "message": ""},
                    "outputs": [{"index": 0, "text": "tok ",
                                 "token_ids": [7], "finish_reason": "",
                                 "logprobs": []}],
                    "finished": False, "finished_on_prefill": False,
                    "delta_seq": seq[sid],
                })
            if mode == "msgpack":
                resp = requests.post(
                    url, data=msgpack.packb({"gens": gens},
                                            use_bin_type=True),
                    headers={"Content-Type": "application/msgpack"},
                    timeout=10)
            else:
                resp = requests.post(url, json={"gens": gens}, timeout=10)
            assert resp.status_code == 200, resp.text
            n += BATCH
        dt = time.perf_counter() - t0
        results[mode] = n / dt
        print(json.dumps({"mode": mode,
                          "deltas_per_s": round(n / dt, 1),
                          "batch": BATCH}))

    print(json.dumps({
        "metric": "generations_ingest_msgpack_vs_json",
        "value": round(results["msgpack"] / results["json"], 3),
        "unit": "x",
        "deltas_per_s": round(results["msgpack"], 1),
    }))
    master.stop()
    store.close()


if __name__ == "__main__":
    main()
