"""Device-path KV transfer for PD disaggregation.

Reference analog: the engine-side RDMA contract negotiated through Link
ops (`/root/reference/xllm_service/scheduler/managers/instance_mgr.cpp:
1087-1113` — `device_ips/ports/k,v_cache_ids` exchanged so prefill KV
never bounces through a host). On TPU the equivalent transport is the JAX
transfer server (`jax.experimental.transfer`): the prefill engine offers
the extracted KV pages as *device* buffers under a request-derived id,
and the decode engine pulls them device-to-device (ICI within a slice,
DCN fabric across slices) — no host serialization on either side.

The control hop stays on the existing `/rpc/kv_transfer` HTTP endpoint:
instead of the msgpack blob, the prefill side sends a small descriptor
`{addr, uuid, shape, dtype}`. The host-msgpack path remains as fallback
whenever either side lacks a transfer server (or the pull fails), behind
the same `PrefillHandoff` contract.
"""

from __future__ import annotations

import hashlib
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import transfer as _xfer

from ..common.faults import FAULTS
from ..common.tracing import TRACER, TraceContext
from ..devtools import lifecycle as _lifecycle
from ..devtools.locks import make_lock
from ..utils import get_logger

logger = get_logger(__name__)

# An offer the decode peer never pulled (transfer failed mid-flight) is
# dropped after this long so the KV buffers can be freed.
OFFER_TTL_S = 120.0


def transfer_uuid(service_request_id: str, incarnation: str = "") -> int:
    """Stable 63-bit id for one handoff."""
    digest = hashlib.blake2b(
        f"{service_request_id}|{incarnation}".encode(), digest_size=8
    ).digest()
    return int.from_bytes(digest, "big") & 0x7FFF_FFFF_FFFF_FFFF


class BandwidthAccountant:
    """Per-link bandwidth budget + throughput accounting for host-path
    KV streaming. Links are classed ICI-shaped (same slice: chip fabric)
    vs DCN-shaped (cross-slice: data-center network) per SNIPPETS.md;
    each class gets a configurable bytes/s budget (0 = unthrottled).

    Token-bucket pacing: :meth:`debit` records `nbytes` on the link and
    returns how long the caller should sleep to stay inside the budget
    (the PULL side paces — a worker thread sleeping is free; the offer
    side never blocks its event loop). Accounting survives pacing-off, so
    throughput still reports in spans/metrics."""

    def __init__(self, ici_bytes_per_s: float = 0.0,
                 dcn_bytes_per_s: float = 0.0):
        self._budget = {"ici": float(ici_bytes_per_s),
                        "dcn": float(dcn_bytes_per_s)}
        self._lock = make_lock("kv_transfer.bandwidth", order=57)  # lock-order: 57
        # link -> [bytes_total, busy_seconds, bucket_level, bucket_ts]
        self._links: dict[str, list[float]] = {}

    def debit(self, link: str, nbytes: int) -> float:
        """Record `nbytes` moved on `link`; returns pacing sleep
        seconds (0.0 when unthrottled or inside budget)."""
        budget = self._budget.get(link, 0.0)
        now = time.monotonic()
        with self._lock:
            st = self._links.setdefault(link, [0.0, 0.0, 0.0, now])
            st[0] += nbytes
            if budget <= 0.0:
                return 0.0
            # Leak the bucket, then pour this transfer in; the overflow
            # over one budget-second is the pacing debt.
            st[2] = max(0.0, st[2] - (now - st[3]) * budget) + nbytes
            st[3] = now
            # Pacing debt only — busy time (which already includes the
            # caller's pacing sleeps as wall time) arrives once via
            # record_busy; adding sleep_s here too would double-count it
            # and underreport throughput exactly when throttled.
            return max(0.0, (st[2] - budget) / budget)

    def record_busy(self, link: str, seconds: float) -> None:
        """Fold actual wire time into the throughput accounting."""
        with self._lock:
            st = self._links.setdefault(link, [0.0, 0.0, 0.0,
                                               time.monotonic()])
            st[1] += seconds

    def stats(self) -> dict[str, dict[str, float]]:
        with self._lock:
            out = {}
            for link, st in self._links.items():
                out[link] = {
                    "bytes_total": st[0],
                    "busy_seconds": round(st[1], 6),
                    "throughput_bytes_per_s": round(st[0] / st[1], 1)
                    if st[1] > 0 else 0.0,
                    "budget_bytes_per_s": self._budget.get(link, 0.0),
                }
            return out


class StreamOfferTable:
    """Offer side of the chunked streaming transfer: registered blobs are
    served to peers in msgpack frames via ``/rpc/kv_stream_pull`` — many
    blocks per round-trip instead of one monolithic POST. The blob stays
    one contiguous byte buffer here; TTL-expired offers are dropped by
    :meth:`gc` exactly like device-path offers."""

    def __init__(self, default_chunk_bytes: int = 1 << 20):
        self.default_chunk_bytes = max(1, int(default_chunk_bytes))
        self._lock = make_lock("kv_transfer.stream_offers", order=58)  # lock-order: 58
        # uuid -> (bytes, meta, deadline)
        self._offers: dict[int, tuple[bytes, dict, float]] = {}

    def offer(self, service_request_id: str, data: bytes,
              shape: list, dtype: str, incarnation: str = "",
              block_bytes: int = 0,
              ctx: Optional[TraceContext] = None) -> dict[str, Any]:
        """Register `data` for streaming; returns the wire descriptor the
        control message carries (everything the puller needs, including
        the whole-payload checksum)."""
        uid = transfer_uuid(service_request_id, "stream:" + incarnation)
        with TRACER.span("kv_transfer.offer", ctx=ctx, require_ctx=True,
                         request_id=service_request_id, path="stream",
                         nbytes=len(data)):
            # Chaos hook shared with the device path: an injected fault
            # here exercises the caller's inline-payload fallback.
            FAULTS.check("kv_transfer.offer", sid=service_request_id)
            self.gc()
            with self._lock:
                if uid not in self._offers:
                    _lifecycle.note_acquire("stream-offer", key=uid)
                self._offers[uid] = (
                    data,
                    {"shape": list(shape), "dtype": dtype},
                    time.monotonic() + OFFER_TTL_S)
        return {
            "stream_uuid": uid,
            "total_bytes": len(data),
            "chunk_bytes": self.default_chunk_bytes,
            "block_bytes": int(block_bytes),
            "shape": list(shape),
            "dtype": dtype,
            "checksum": hashlib.blake2b(data, digest_size=8).hexdigest(),
        }

    def read_chunk(self, uuid: int, offset: int,
                   max_bytes: int) -> Optional[dict[str, Any]]:
        """One pull round-trip's frame: None for an unknown/expired
        offer (the puller surfaces it and the sender falls back)."""
        with self._lock:
            entry = self._offers.get(int(uuid))
            if entry is None:
                return None
            data, _meta, _dl = entry
        offset = max(0, int(offset))
        chunk = data[offset:offset + max(1, int(max_bytes))]
        return {
            "offset": offset,
            "data": chunk,
            "total_bytes": len(data),
            "last": offset + len(chunk) >= len(data),
        }

    def release(self, uuid: int) -> None:
        with self._lock:
            if self._offers.pop(int(uuid), None) is not None:
                _lifecycle.note_release("stream-offer", key=int(uuid))

    def gc(self) -> None:
        now = time.monotonic()
        with self._lock:
            dead = [u for u, (_, _, dl) in self._offers.items() if dl < now]
            for u in dead:
                self._offers.pop(u, None)
                _lifecycle.note_release("stream-offer", key=u)
        if dead:
            logger.warning("dropped %d expired KV stream offers", len(dead))

    def count(self) -> int:
        with self._lock:
            return len(self._offers)


def pull_stream(peer_addr: str, desc: dict[str, Any],
                accountant: Optional[BandwidthAccountant] = None,
                link: str = "dcn",
                post=None,
                ctx: Optional[TraceContext] = None,
                deadline_s: float = 45.0) -> "Any":
    """Pull a streamed KV payload from `peer_addr` in chunked round-trips
    (runs in an executor thread — pacing sleeps are free here). Returns
    the reassembled numpy array; raises ValueError on a bad frame or
    checksum mismatch (the peer's retry then rides the inline fallback).

    `deadline_s` bounds the WHOLE pull, pacing included — it must stay
    under the sender's handoff POST timeout (60 s) so a slow/throttled
    pull fails on THIS side first and the sender's inline retry finds the
    handoff unclaimed, instead of the sender abandoning a pull that is
    still running.

    `post(url, payload_dict) -> response_dict` is injectable for tests;
    the default POSTs msgpack to ``/rpc/kv_stream_pull``."""
    import numpy as np

    if post is None:
        import msgpack
        import requests as _requests

        session = _requests.Session()

        def post(url, payload):   # pragma: no cover - trivial transport
            r = session.post(url, data=msgpack.packb(payload,
                                                     use_bin_type=True),
                             headers={"Content-Type":
                                      "application/msgpack"},
                             timeout=30)
            r.raise_for_status()
            return msgpack.unpackb(r.content, raw=False)

    url = f"http://{peer_addr}/rpc/kv_stream_pull"
    total = int(desc["total_bytes"])
    chunk_bytes = max(1, int(desc.get("chunk_bytes") or (1 << 20)))
    buf = bytearray(total)
    got = 0
    t0 = time.monotonic()
    with TRACER.span("kv_transfer.pull", ctx=ctx, require_ctx=True,
                     path="stream", nbytes=total, link=link) as span:
        while got < total:
            if time.monotonic() - t0 > deadline_s:
                raise TimeoutError(
                    f"stream pull exceeded {deadline_s:.0f}s deadline at "
                    f"{got}/{total} bytes (budget too tight for this "
                    "payload — the sender's retry rides the inline path)")
            # Chaos hook: a mid-stream pull fault aborts THIS transfer;
            # the prefill side retries via the inline host path.
            FAULTS.check("kv_transfer.pull", uuid=desc.get("stream_uuid"))
            frame = post(url, {"uuid": desc["stream_uuid"],
                               "offset": got,
                               "max_bytes": chunk_bytes})
            if not frame or frame.get("data") is None:
                raise ValueError("stream offer expired or unknown")
            data = frame["data"]
            if not data:
                raise ValueError("empty stream frame")
            buf[got:got + len(data)] = data
            got += len(data)
            if accountant is not None:
                sleep_s = accountant.debit(link, len(data))
                if sleep_s > 0:
                    time.sleep(sleep_s)
        elapsed = max(1e-9, time.monotonic() - t0)
        if accountant is not None:
            accountant.record_busy(link, elapsed)
        span.set(mbps=round(total / elapsed / 1e6, 3),
                 round_trips=-(-total // chunk_bytes))
    digest = hashlib.blake2b(buf, digest_size=8).hexdigest()
    if desc.get("checksum") and digest != desc["checksum"]:
        raise ValueError("stream checksum mismatch")
    if desc.get("dtype") == "bfloat16":
        import ml_dtypes

        np_dtype = np.dtype(ml_dtypes.bfloat16)
    else:
        np_dtype = np.dtype(desc["dtype"])
    # frombuffer over the bytearray: zero-copy AND writable (a bytes copy
    # would yield a read-only array that defeats downstream donation).
    return np.frombuffer(buf, dtype=np_dtype).reshape(desc["shape"])


class KvTransferManager:
    """One per engine agent: owns a transfer server bound to the engine's
    backend and a cache of connections to peer servers. For sharded
    engines (TP over the model axis) the pull reconstructs the same
    partition spec on the receiving mesh — shards move device-to-device
    without ever being gathered (requires the PD pair to advertise
    identical mesh topologies; the agent gates on that)."""

    def __init__(self, device: jax.Device, listen_ip: str = "127.0.0.1",
                 mesh=None):
        self._device = device
        self._mesh = mesh
        self._server = _xfer.start_transfer_server(
            device.client, f"{listen_ip}:0", [f"{listen_ip}:0"])
        self._conns: dict[str, Any] = {}
        self._lock = make_lock("kv_transfer.pending", order=56)  # lock-order: 56
        # uuid -> (arrays, deadline): keeps offered buffers alive until the
        # peer confirms the pull (release()) or the TTL lapses.
        self._pending: dict[int, tuple[Any, float]] = {}

    @classmethod
    def create(cls, device: jax.Device, listen_ip: str = "127.0.0.1",
               mesh=None) -> Optional["KvTransferManager"]:
        """None when the transfer server cannot be started on this
        device — said loudly, because every handoff then takes the
        host-msgpack path."""
        try:
            return cls(device, listen_ip, mesh=mesh)
        except Exception as e:  # noqa: BLE001 — optional capability
            logger.warning("device KV transfer server failed to start; PD "
                           "handoffs will take the host path: %s", e)
            return None

    @property
    def address(self) -> str:
        return self._server.address()

    # ------------------------------------------------------------ prefill
    def offer(self, service_request_id: str, blob: jax.Array,
              incarnation: str = "",
              ctx: Optional[TraceContext] = None) -> dict[str, Any]:
        """Schedule `blob` for a device-to-device pull; returns the wire
        descriptor for the control message. `ctx` parents the offer span
        under the request's carried trace context."""
        uid = transfer_uuid(service_request_id, incarnation)
        with TRACER.span("kv_transfer.offer", ctx=ctx, require_ctx=True,
                         request_id=service_request_id, path="device",
                         shape=list(blob.shape)):
            # Chaos hook: an injected error here lands in the agent's
            # existing device-path try/except, exercising the host-msgpack
            # fallback (and stamps a fault event on the offer span).
            FAULTS.check("kv_transfer.offer", sid=service_request_id)
            self.gc()
            with self._lock:
                self._pending[uid] = ([blob], time.monotonic() + OFFER_TTL_S)
            self._server.await_pull(uid, [blob])
        desc = {
            "addr": self.address,
            "uuid": uid,
            "shape": list(blob.shape),
            "dtype": str(blob.dtype),
        }
        sharding = getattr(blob, "sharding", None)
        if isinstance(sharding, jax.sharding.NamedSharding):
            # Partition spec rebuilt on the receiving mesh (identical
            # topology, gated by the agent). Axis entries are
            # None | str | tuple[str,...].
            desc["spec"] = [list(p) if isinstance(p, tuple) else p
                            for p in sharding.spec]
        return desc

    def release(self, uuid: int) -> None:
        with self._lock:
            self._pending.pop(uuid, None)

    def gc(self) -> None:
        """Drop expired offers so their KV buffers can be freed. Called on
        every offer AND from the agent's heartbeat loop — an idle agent
        must still release buffers whose peer died before pulling."""
        now = time.monotonic()
        with self._lock:
            dead = [u for u, (_, dl) in self._pending.items() if dl < now]
            for u in dead:
                self._pending.pop(u, None)
        if dead:
            logger.warning("dropped %d expired KV-transfer offers", len(dead))

    def close(self) -> None:
        """Drop all held references (offered buffers, peer connections).
        The underlying server socket is freed with the object."""
        with self._lock:
            self._pending.clear()
            self._conns.clear()
        self._server = None

    # ------------------------------------------------------------- decode
    def pull(self, desc: dict[str, Any],
             ctx: Optional[TraceContext] = None) -> jax.Array:
        """Pull the offered KV pages straight into this engine's device
        memory. `ctx` parents the pull span under the request's carried
        trace context."""
        with TRACER.span("kv_transfer.pull", ctx=ctx, require_ctx=True,
                         path="device", shape=list(desc.get("shape", ()))):
            # Chaos hook: decode-side pull failure (the receiving agent's
            # handoff handler reports UNAVAILABLE back to the service,
            # which is exactly the path a mid-transfer network fault
            # takes).
            FAULTS.check("kv_transfer.pull", uuid=desc.get("uuid"))
            addr = desc["addr"]
            with self._lock:
                conn = self._conns.get(addr)
            if conn is None:
                conn = self._server.connect(addr)
                with self._lock:
                    self._conns[addr] = conn
            pspec = desc.get("spec")
            if pspec is not None and self._mesh is not None:
                sharding = jax.sharding.NamedSharding(
                    self._mesh,
                    jax.sharding.PartitionSpec(
                        *[tuple(p) if isinstance(p, list) else p
                          for p in pspec]))
            else:
                sharding = jax.sharding.SingleDeviceSharding(self._device)
            spec = jax.ShapeDtypeStruct(
                tuple(desc["shape"]), jnp.dtype(desc["dtype"]),
                sharding=sharding)
            out = conn.pull(int(desc["uuid"]), [spec])
            return out[0]
