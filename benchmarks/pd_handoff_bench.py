"""PD KV-handoff latency: device transfer path vs host msgpack path.

The reference justifies its engine-side RDMA link negotiation with "KV
must never bounce through a host" (instance_mgr.cpp:1087-1113). Our
device path is the JAX transfer server (engine/kv_transfer.py); the
fallback is msgpack-over-HTTP with the blob inline (engine/agent.py
pack_handoff). This times BOTH at bench-1b KV shapes for 2k and 8k
contexts — per handoff, including the loopback HTTP hop the real
fallback pays — and prints one JSON line. The device path must win or
be demoted.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from xllm_service_tpu.common.request import SamplingParams
    from xllm_service_tpu.engine.agent import pack_handoff, unpack_handoff
    from xllm_service_tpu.engine.engine import PrefillHandoff
    from xllm_service_tpu.engine.kv_transfer import KvTransferManager

    from _chip import require_tpu

    device = require_tpu()
    dev = jax.devices()[0]

    # bench-1b KV shapes: [L, 2, n_pages, n_kv, ps, hd].
    L, n_kv, ps, hd = 16, 8, 16, 128
    dtype = jnp.bfloat16
    ctxs = (2048, 8192)

    # Host-path receiver: the loopback HTTP hop the real fallback pays.
    received: dict = {}

    class _H(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers["Content-Length"])
            body = self.rfile.read(n)
            obj = unpack_handoff(body)
            # Decode side uploads the blob to its device (the cost the
            # device path exists to avoid).
            received["kv"] = jax.device_put(
                jnp.asarray(obj["kv_blob"]), dev)
            received["kv"].block_until_ready()
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", 0), _H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    host_url = f"http://127.0.0.1:{srv.server_port}/rpc/kv_transfer"

    import requests

    mgr_p = KvTransferManager.create(dev)
    mgr_d = KvTransferManager.create(dev)

    result = {"device": device,
              "metric": "pd_handoff_ms_per_transfer", "unit": "ms",
              "device_transfer_available": mgr_p is not None}

    sampling = SamplingParams(max_tokens=16)
    for ctx in ctxs:
        n_pages = ctx // ps
        blob = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(0),
                              (L, 2, n_pages, n_kv, ps, hd), dtype), dev)
        blob.block_until_ready()
        mb = blob.size * blob.dtype.itemsize / 1e6
        row: dict = {"ctx": ctx, "blob_mb": round(mb, 1)}

        # --- device path: offer + pull (device-to-device) ------------
        if mgr_p is not None and mgr_d is not None:
            try:
                times = []
                for i in range(5):
                    t0 = time.perf_counter()
                    desc = mgr_p.offer(f"bench-{ctx}-{i}", blob)
                    out = mgr_d.pull(desc)
                    out.block_until_ready()
                    mgr_p.release(desc["uuid"])
                    times.append(time.perf_counter() - t0)
                    del out
                row["device_ms"] = round(min(times) * 1e3, 2)
                row["device_gbps"] = round(mb / 1e3 / min(times), 2)
            except Exception as e:  # noqa: BLE001 — record, keep going
                row["device_error"] = f"{type(e).__name__}: {e}"[:300]

        # --- host path: pack (device_get+msgpack) → HTTP → unpack+put -
        h = PrefillHandoff(
            service_request_id=f"bench-{ctx}", request_id="r0",
            token_ids=list(range(ctx)), first_token=1,
            first_logprob=None, sampling=sampling, kv_blob=blob)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            data = pack_handoff(h, "127.0.0.1:0")
            r = requests.post(host_url, data=data,
                              headers={"Content-Type":
                                       "application/msgpack"})
            assert r.status_code == 200
            times.append(time.perf_counter() - t0)
        row["host_ms"] = round(min(times) * 1e3, 2)
        row["host_gbps"] = round(mb / 1e3 / min(times), 2)
        if "device_ms" in row and row["device_ms"] > 0:
            row["device_speedup"] = round(row["host_ms"] / row["device_ms"],
                                          2)
        result[f"ctx_{ctx}"] = row
        del blob
        received.clear()

    srv.shutdown()
    # Headline value: device-path ms at the largest context measured.
    last = result.get(f"ctx_{ctxs[-1]}", {})
    result["value"] = last.get("device_ms", last.get("host_ms", 0.0))
    print(json.dumps(result))


if __name__ == "__main__":
    main()
