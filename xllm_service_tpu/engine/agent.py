"""Engine instance agent: the process that owns one TPU engine and speaks
the orchestration wire contract.

Parity: the per-instance responsibilities implied by the reference
(SURVEY.md §3.4 + `rpc_service/client.cpp` SDK): register in coordination
under `XLLM:INSTANCE:<TYPE>:<name>` with a TTL lease + incarnation id,
heartbeat every 3s with KvCacheEvents + Load/LatencyMetrics, accept
enriched Completions/ChatCompletions, stream batched Generations to the
service's RPC endpoint, serve /health probes, honor Link/Unlink/Cancel and
dynamic role flips.

Run: ``python -m xllm_service_tpu.engine.agent --coordination-addr ...``
"""

from __future__ import annotations

import argparse
import asyncio
import json
import queue
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import msgpack
import numpy as np
import requests as _requests
from aiohttp import web

import jax

from ..common import flightrecorder, tracing
from ..common import topology as topo
from ..common.flightrecorder import RECORDER
from ..common.metrics import (
    ENGINE_HEARTBEATS_TOTAL,
    ENGINE_PEER_LINKED,
    evict_series,
)
from ..devtools import lifecycle as _lifecycle
from ..common.request import LogProb, RequestOutput, SamplingParams, Status, StatusCode
from ..common.tracing import NOOP_SPAN, TRACER, TraceContext
from ..common.types import (InstanceMetaInfo, InstanceType, TpuTopology,
                            now_ms)
from ..devtools.locks import make_lock
from ..coordination import CoordinationClient, connect
from ..profiling import PROFILER
from ..profiling import handle_admin_profile as _handle_admin_profile
from ..rpc import MASTER_KEY, instance_key
from ..rpc import wire as dispatch_wire
from ..chat_template import MM_PLACEHOLDER, JinjaChatTemplate
from ..tokenizer import TokenizerFactory
from ..utils import get_local_ip, get_logger, pick_free_port
from .config import EngineConfig, prefill_bucket_ladder
from .engine import EngineRequest, InferenceEngine, PrefillHandoff
from .telemetry import summarize as summarize_telemetry

logger = get_logger(__name__)


def pack_handoff(h: PrefillHandoff, source_service_addr: str,
                 kv_ref: Optional[dict] = None,
                 source_instance: str = "",
                 trace_context: Optional[dict] = None,
                 kv_stream: Optional[dict] = None) -> bytes:
    """Serialize a PD handoff control message. With `kv_ref` (device
    transfer path) the KV stays on device and only the pull descriptor is
    sent; with `kv_stream` the host bytes are pulled back in chunked
    frames (streaming multi-block transfer, bandwidth-accounted);
    otherwise the blob is downloaded and carried inline (DCN host path;
    msgpack + raw array bytes, bf16 as ml_dtypes bytes).
    `source_instance` identifies the sending prefill instance — the decode
    side only accepts handoffs from linked peers."""
    lp = h.first_logprob
    msg: dict[str, Any] = {
        "service_request_id": h.service_request_id,
        "request_id": h.request_id,
        "source_service_addr": source_service_addr,
        "source_instance": source_instance,
        "token_ids": h.token_ids,
        "first_token": h.first_token,
        "first_logprob": None if lp is None else {
            "token": lp.token, "token_id": lp.token_id,
            "logprob": lp.logprob,
            "top": [(t.token, t.token_id, t.logprob)
                    for t in lp.top_logprobs]},
        "sampling": h.sampling.to_dict(),
    }
    if trace_context is not None:
        msg["trace_context"] = trace_context
    if kv_ref is not None:
        msg["kv_ref"] = kv_ref
    elif kv_stream is not None:
        msg["kv_stream"] = kv_stream
    else:
        blob = np.asarray(h.kv_blob)
        msg["kv"] = {"bytes": blob.tobytes(),
                     "shape": list(blob.shape),
                     "dtype": str(blob.dtype)}
    return msgpack.packb(msg, use_bin_type=True)


def unpack_handoff(data: bytes) -> dict:
    obj = msgpack.unpackb(data, raw=False)
    kv = obj.get("kv")
    if kv is not None:
        dtype = kv["dtype"]
        if dtype == "bfloat16":
            import ml_dtypes

            np_dtype = ml_dtypes.bfloat16
        else:
            np_dtype = np.dtype(dtype)
        obj["kv_blob"] = np.frombuffer(kv["bytes"], dtype=np_dtype).reshape(
            kv["shape"])
    return obj


@dataclass
class AgentConfig:
    host: str = "127.0.0.1"
    port: int = 0                      # 0 = ephemeral
    coordination_addr: str = ""
    coordination_namespace: str = ""
    instance_type: InstanceType = InstanceType.MIX
    model_id: str = "tiny-llama"
    tokenizer_path: str = ""
    heartbeat_interval_s: float = 3.0
    lease_ttl_s: float = 3.0
    generation_flush_ms: float = 5.0   # batching window for Generations
    # Telemetry wiring (ISSUE 15): "mux" = ONE multiplexed keepalive
    # session to the owning master (tagged hb+gens frames on
    # /rpc/telemetry; O(1) connections per engine), "owner" = heartbeats
    # to the rendezvous owner but deltas direct per-dest, "master" = the
    # legacy funnel (heartbeats to the elected master only).
    telemetry_mode: str = "mux"
    # Coordination-plane static stability: "on" keeps heartbeats flowing
    # to the last-known-good telemetry owner / elected master while the
    # coordination plane is unreachable (owner resolution comes back
    # empty), so the masters' degraded-mode liveness fallback (direct
    # heartbeat silence) sees this engine alive through a total outage.
    # "off" restores the legacy behavior: no resolvable target, no
    # beats.
    degraded_mode: str = "on"
    slice_id: str = "slice-0"
    # Topology placement coordinate (common/topology.py). A non-empty
    # topo_host marks this instance as PLACED: routing, planner flips,
    # and autoscaler spawns then cost its PD links by class. Empty (the
    # default) keeps the legacy per-host synthetic coordinate — flat
    # fleets behave exactly as before.
    topo_host: str = ""
    topo_chip: int = -1
    # Model replicas behind this one registration (reference dp_size,
    # `xllm_rpc_service.proto:40-43`): each replica is an independent
    # continuous-batching engine; requests are dispatched prefix-affine
    # with a load guard. Replicas land on local devices round-robin.
    dp_size: int = 1
    # Device-path PD KV transfer (JAX transfer server). Auto-disabled when
    # the runtime lacks support; sharded engines use it only with peers
    # advertising an identical mesh topology (shard layouts must line
    # up) — mismatched pairs fall back to the host path.
    enable_device_kv_transfer: bool = True
    # Host-path streaming transfer (engine/kv_transfer.py StreamOfferTable
    # + pull_stream): payloads at or above the threshold are pulled back
    # in chunked msgpack frames — many blocks per round-trip — instead of
    # one monolithic inline POST. 0 threshold streams everything; a
    # negative threshold disables streaming.
    kv_stream_threshold_bytes: int = 256 * 1024
    kv_stream_chunk_bytes: int = 1 << 20
    # Per-link-class bandwidth budgets, bytes/s (0 = unthrottled): links
    # to a peer on the SAME slice are ICI-shaped, cross-slice links are
    # DCN-shaped. The pull side paces to the budget; throughput reports
    # in spans and /stats either way.
    ici_bytes_per_s: float = 0.0
    dcn_bytes_per_s: float = 0.0


class _ChoiceAggregator:
    """Merges n engine sequences into one OpenAI request: re-indexes each
    choice's outputs and defers `finished`/usage until the last choice
    completes."""

    def __init__(self, n: int, push):
        self._n = n
        self._remaining = n
        self._push = push
        self._prompt_tokens = 0
        self._generated = 0
        self._lock = make_lock("agent.choice_aggregator", order=60)  # lock-order: 60

    def callback_for(self, index: int):
        def cb(out: RequestOutput) -> None:
            for seq_out in out.outputs:
                seq_out.index = index
            if out.finished:
                with self._lock:
                    self._remaining -= 1
                    last = self._remaining == 0
                    if out.usage is not None:
                        self._prompt_tokens = out.usage.num_prompt_tokens
                        self._generated += out.usage.num_generated_tokens
                    if last:
                        from ..common.request import Usage

                        out.usage = Usage(
                            num_prompt_tokens=self._prompt_tokens,
                            num_generated_tokens=self._generated)
                    else:
                        out.finished = False
                        out.usage = None
            self._push(out)
        return cb


_NOTHING = object()   # queue-timeout marker distinct from the stop sentinel


class GenerationStreamer:
    """Batches RequestOutput deltas per destination service and POSTs
    `{"gens": [...]}` (reference batched DisaggStreamGenerations,
    `rpc_service/service.cpp:149-215`). `engine` is anything with a
    `cancel(service_request_id)` — the agent passes itself to fan
    cancellations across dp replicas.

    Delivery semantics: each delta carries a per-request monotonic
    `delta_seq` (the service dedupes on it, so retries are safe even when
    the original POST was processed but its response lost). A failed dest
    keeps its gens queued per-dest and is retried after a backoff WITHOUT
    blocking flushes to healthy dests; only after `FLUSH_RETRIES`
    consecutive failures are that dest's requests cancelled.

    Multiplexed session (ISSUE 15): with an `owner_fn`, every ready
    dest's batch rides ONE tagged-frame POST to the engine's owning
    master (`/rpc/telemetry`), which ingests its own dests and relays
    the rest master->master — so this engine's fan-out is one keepalive
    connection regardless of how many masters dispatched to it. The
    per-dest retry/cancel machinery is unchanged: the owner's response
    carries per-dest delivery verdicts. A legacy owner (404) demotes the
    streamer to the direct per-dest wire for the process's lifetime."""

    # One transient blip (service GC pause, connection reset) must not kill
    # every in-flight stream on the instance: retry before cancelling.
    FLUSH_RETRIES = 2
    RETRY_BACKOFF_S = 0.25

    def __init__(self, engine: InferenceEngine, flush_ms: float,
                 session: Optional[_requests.Session] = None,
                 owner_fn=None):
        self._engine = engine
        self._q: "queue.Queue[Optional[tuple[str, dict]]]" = queue.Queue()
        self._flush_s = flush_ms / 1000.0
        self._seq_lock = make_lock("agent.streamer_seq", order=62)  # lock-order: 62
        self._seqs: dict[str, int] = {}
        # Sender identity stamped on every delta (set by the agent once its
        # address/incarnation are known; empty = unstamped, accepted as-is).
        self.instance_name = ""
        self.incarnation = ""
        # Shared bounded keepalive session (None = a private one per
        # streamer, the legacy shape) and the telemetry-owner resolver
        # enabling the multiplexed wire (None = direct per-dest POSTs).
        self._session = session
        self._owner_fn = owner_fn
        self._mux_ok = owner_fn is not None
        self.mux_sends = 0
        self.direct_sends = 0
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="gen-streamer")
        self._thread.start()

    def push(self, dest_addr: str, output: RequestOutput) -> None:
        sid = output.service_request_id
        # seq assignment AND enqueue under one lock: the scheduler's dedup
        # relies on queue order == seq order per request, which concurrent
        # pushers would otherwise break (later seq enqueued first → earlier
        # delta dropped as a "duplicate").
        with self._seq_lock:
            seq = self._seqs.get(sid, 0) + 1
            if output.finished:
                self._seqs.pop(sid, None)
            else:
                self._seqs[sid] = seq
            output.delta_seq = seq
            output.instance = self.instance_name
            output.incarnation = self.incarnation
            self._q.put((dest_addr, output.to_dict()))

    def _loop(self) -> None:
        session = self._session or _requests.Session()
        # Per-dest unsent gens (order preserved) + failure bookkeeping.
        pending: dict[str, list[dict]] = {}
        attempts: dict[str, int] = {}
        next_try: dict[str, float] = {}
        stopping = False
        while True:
            now = time.monotonic()
            if stopping and not pending:
                return
            if pending:
                wait = max(0.0, min(next_try.get(d, now)
                                    for d in pending) - now)
            else:
                wait = None   # idle: block until the next delta
            try:
                item = self._q.get(timeout=wait)
            except queue.Empty:
                item = _NOTHING
            if item is None:
                stopping = True
            elif item is not _NOTHING:
                # Batch for one flush interval, preserving per-dest order.
                pending.setdefault(item[0], []).append(item[1])
                deadline = time.monotonic() + self._flush_s
                while True:
                    timeout = deadline - time.monotonic()
                    if timeout <= 0:
                        break
                    try:
                        nxt = self._q.get(timeout=timeout)
                    except queue.Empty:
                        break
                    if nxt is None:
                        stopping = True
                        break
                    pending.setdefault(nxt[0], []).append(nxt[1])

            now = time.monotonic()
            ready = [d for d in list(pending)
                     if stopping or next_try.get(d, 0.0) <= now]
            outcomes = self._flush_ready(session, ready, pending)
            for dest in ready:
                if outcomes.get(dest, False):
                    del pending[dest]
                    attempts.pop(dest, None)
                    next_try.pop(dest, None)
                else:
                    n = attempts.get(dest, 0) + 1
                    if stopping or n > self.FLUSH_RETRIES:
                        # Repeatedly unreachable: cancel these requests so
                        # the engine doesn't burn chips on a dead stream.
                        for g in pending.pop(dest):
                            self._engine.cancel(
                                g.get("service_request_id", ""))
                        attempts.pop(dest, None)
                        next_try.pop(dest, None)
                    else:
                        attempts[dest] = n
                        next_try[dest] = now + self.RETRY_BACKOFF_S * n

    def _flush_ready(self, session: _requests.Session, dests: list,
                     pending: dict) -> dict:
        """One flush pass over the ready dests → per-dest delivery
        verdicts. Multiplexed wire when an owner is resolvable, direct
        per-dest POSTs otherwise (or after a legacy-owner demotion)."""
        if not dests:
            return {}
        if self._mux_ok:
            owner = self._owner_fn()
            if owner:
                out = self._send_mux(session, owner,
                                     {d: pending[d] for d in dests})
                if out is not None:
                    return out
        self.direct_sends += len(dests)
        return {d: self._send(session, d, pending[d]) for d in dests}

    def _send_mux(self, session: _requests.Session, owner: str,
                  batches: dict) -> Optional[dict]:
        """All ready batches as tagged frames on ONE POST to the owning
        master. Returns per-dest verdicts, or None after a legacy-owner
        demotion (caller falls back to the direct wire THIS pass)."""
        frames = [{"t": "gens", "dest": d, "d": {"gens": gens}}
                  for d, gens in batches.items()]
        body, ctype = dispatch_wire.encode_telemetry(frames)
        try:
            r = session.post(f"http://{owner}/rpc/telemetry", data=body,
                             headers={"Content-Type": ctype}, timeout=10)
            if r.status_code in (404, 405):
                logger.warning("telemetry owner %s lacks /rpc/telemetry; "
                               "demoting streamer to the direct per-dest "
                               "wire", owner)
                self._mux_ok = False
                return None
            r.raise_for_status()
            payload = r.json()
        except (_requests.RequestException, ValueError) as e:
            logger.warning("multiplexed gens push via %s failed: %s",
                           owner, e)
            note = getattr(self._owner_fn, "note_failure", None)
            if note is not None:
                # Owner death: the resolver excludes it so the next flush
                # targets the rendezvous successor (same successor rule
                # as the service-side handoff relay).
                note(owner)
            return {d: False for d in batches}
        self.mux_sends += 1
        for sid, ok in (payload.get("alive") or {}).items():
            if not ok:
                self._engine.cancel(sid)
        dest_ok = payload.get("dest_ok") or {}
        return {d: bool(dest_ok.get(d, False)) for d in batches}

    def _send(self, session: _requests.Session, dest: str,
              gens: list[dict]) -> bool:
        try:
            # msgpack framing: the hottest wire in the system (every token
            # batch of every stream) — binary beats JSON both to encode
            # here and to parse on the service (reference ships batched
            # protobuf on this hop for the same reason).
            r = session.post(
                f"http://{dest}/rpc/generations",
                data=msgpack.packb({"gens": gens}, use_bin_type=True),
                headers={"Content-Type": "application/msgpack"},
                timeout=10)
            # An error page (4xx/5xx) must route through retry/cancel,
            # not count as delivery.
            r.raise_for_status()
            alive = r.json().get("alive", {})
            for sid, ok in alive.items():
                if not ok:
                    self._engine.cancel(sid)
            return True
        except (_requests.RequestException, ValueError) as e:
            logger.warning("generations push to %s failed: %s", dest, e)
            return False

    def stop(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=15)


class EngineAgent:
    def __init__(self, engine_cfg: EngineConfig, agent_cfg: AgentConfig,
                 coord: Optional[CoordinationClient] = None,
                 params: Optional[dict] = None):
        self.cfg = agent_cfg
        self.coord = coord or connect(agent_cfg.coordination_addr,
                                      agent_cfg.coordination_namespace)
        tokenizer = TokenizerFactory.create_tokenizer(agent_cfg.tokenizer_path)
        self.chat_template = JinjaChatTemplate(
            TokenizerFactory.load_chat_template(agent_cfg.tokenizer_path))
        dp = max(1, agent_cfg.dp_size)
        if dp > 1 and engine_cfg.mesh:
            logger.warning("dp_size>1 with an engine-internal mesh is not "
                           "supported yet; forcing dp_size=1")
            dp = 1
        devs = jax.devices()
        self.engines: list[InferenceEngine] = []
        for i in range(dp):
            dev = devs[i % len(devs)]
            ecfg_i = engine_cfg
            if i > 0 and engine_cfg.kv_tier_ssd_path:
                # Each replica owns its own TieredKVStore; a shared spill
                # path would have replica i's open('w+b') truncate the
                # file under replica 0's live mmap.
                import dataclasses

                ecfg_i = dataclasses.replace(
                    engine_cfg,
                    kv_tier_ssd_path=f"{engine_cfg.kv_tier_ssd_path}.{i}")
            with jax.default_device(dev):
                if i == 0:
                    eng = InferenceEngine(ecfg_i, tokenizer=tokenizer,
                                          params=params)
                else:
                    # Replicate the first replica's weights (same values on
                    # every replica; a copy only when the device differs).
                    eng = InferenceEngine(
                        ecfg_i, tokenizer=tokenizer,
                        params=jax.device_put(self.engines[0].params, dev))
            self.engines.append(eng)
            logger.info("engine %d holds %s", i, eng.device_report())
        # Multi-host lockstep (parallel/multihost.py): this agent runs on
        # the primary host only; submit/cancel are mirrored to follower
        # hosts and the engine steps collectively in the proxy's tick
        # loop (engine/multihost_driver.py).
        if jax.process_count() > 1:
            from .multihost_driver import (
                MultihostEngineDriver,
                MultihostEngineProxy,
            )

            if dp != 1:
                raise ValueError("multihost mode requires dp_size == 1")
            self.engines = [MultihostEngineProxy(
                MultihostEngineDriver(self.engines[0]))]  # type: ignore
        self.engine = self.engines[0]   # config/metadata accessor
        self._rr_replica = 0
        self.port = agent_cfg.port or pick_free_port(agent_cfg.host)
        self.name = f"{agent_cfg.host}:{self.port}"
        self.incarnation_id = uuid.uuid4().hex[:12]
        self.instance_type = agent_cfg.instance_type
        # Heartbeat wire format: msgpack (KV-event keys ride as raw 16
        # bytes) until a legacy master rejects it, then JSON for the rest
        # of THAT master's life — a new master (failover/re-election) may
        # be a newer build, so the demotion resets when the master
        # address changes.
        self._hb_wire = dispatch_wire.WIRE_MSGPACK
        self._hb_master = ""
        # ONE shared, bounded keepalive session for every telemetry hop
        # this agent makes (heartbeats + delta pushes): the engine-side
        # half of the O(engines) fan-out story. The owner resolver
        # mirrors the SERVICE membership and applies the same rendezvous
        # shard map the masters use.
        from ..multimaster import TelemetryOwnerResolver
        from ..rpc.channel import make_keepalive_session
        self.telemetry_session = make_keepalive_session()
        self.telemetry_owner = TelemetryOwnerResolver(
            self.coord, self.name,
            hold_last_owner=agent_cfg.degraded_mode != "off")
        self._telemetry_mode = agent_cfg.telemetry_mode
        # Last master address that resolved ("master" funnel mode): the
        # degraded-mode fallback target while the plane is unreachable.
        self._last_master = ""
        # Pass the agent itself: cancel() fans out across replicas.
        self.streamer = GenerationStreamer(
            self, agent_cfg.generation_flush_ms,
            session=self.telemetry_session,
            owner_fn=self.telemetry_owner
            if self._telemetry_mode == "mux" else None)
        # Stamp sender identity on every delta: after a transparent
        # failover the service drops deltas from incarnations the request
        # is no longer bound to.
        self.streamer.instance_name = self.name
        self.streamer.incarnation = self.incarnation_id
        # Agent-observed TTFT per request (ms, accept -> first delta);
        # serve_bench reads this to split client TTFT into agent-side vs
        # master/wire cost (span profiling).
        self.ttft_spans: deque = deque(maxlen=512)
        self.kv_transfer = None
        if agent_cfg.enable_device_kv_transfer:
            from .kv_transfer import KvTransferManager

            dev = next(iter(self.engine.kv_pages.devices()))
            self.kv_transfer = KvTransferManager.create(
                dev, agent_cfg.host, mesh=self.engine.mesh)
            if self.kv_transfer is not None:
                logger.info("device KV transfer server on %s",
                            self.kv_transfer.address)
        # Host-path streaming transfer: offer table served via
        # /rpc/kv_stream_pull + per-link bandwidth accounting (ICI vs DCN
        # shaped by peer slice id).
        from .kv_transfer import BandwidthAccountant, StreamOfferTable

        self.kv_stream = StreamOfferTable(agent_cfg.kv_stream_chunk_bytes)
        self.bandwidth = BandwidthAccountant(agent_cfg.ici_bytes_per_s,
                                             agent_cfg.dcn_bytes_per_s)
        self.kv_stream_sent = 0
        self.kv_stream_received = 0
        self.linked_peers: dict[str, InstanceMetaInfo] = {}
        # Handoff idempotency: sid -> receive time. A device-path control
        # POST whose response is lost makes the prefill side retry via the
        # host path; without this the same sequence would inject twice.
        self._handoffs_seen: dict[str, float] = {}
        self._draining = False
        self.encode_count = 0
        # PD transfer-path telemetry (also surfaced in /stats).
        self.kv_device_sent = 0
        self.kv_host_sent = 0
        self.kv_device_received = 0
        self.kv_host_received = 0
        self._alive = True
        self._profiler_started = False
        self._started = threading.Event()
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._runner: Optional[web.AppRunner] = None
        self._threads: list[threading.Thread] = []
        # Anomaly flight recorder: this agent's bundles carry the engine
        # state (queue depth, tier stats, transfer counters) at anomaly
        # time; served at /admin/flightrecorder/recent.
        RECORDER.add_context_provider("engine", self._anomaly_context)

    def _anomaly_context(self) -> dict[str, Any]:
        return {
            "instance": self.name,
            "incarnation": self.incarnation_id,
            "stats": self.aggregate_stats(),
            "kv_tier": self._tier_stats(),
            "kv_transfer": {
                "device_sent": self.kv_device_sent,
                "host_sent": self.kv_host_sent,
                "stream_sent": self.kv_stream_sent,
                "stream_received": self.kv_stream_received,
            },
        }

    # --------------------------------------------------------- dp dispatch
    def cancel(self, service_request_id: str) -> None:
        """Fan a cancellation across all replicas (each ignores unknown
        ids)."""
        for eng in self.engines:
            eng.cancel(service_request_id)

    def _pick_engine(self, token_ids: list[int]) -> InferenceEngine:
        """Replica dispatch: prefix-affine (the same prompt prefix lands on
        the same replica, so its prefix cache actually hits) with a load
        guard (spill to the least-loaded replica when the affine one is a
        full batch deeper than the lightest)."""
        if len(self.engines) == 1:
            return self.engines[0]
        block = self.engine.cfg.hash_block_size
        key = hash(tuple(token_ids[:block])) if token_ids else self._rr_replica
        self._rr_replica += 1
        affine = self.engines[key % len(self.engines)]

        def depth(e: InferenceEngine) -> int:
            s = e.stats()
            return s["waiting"] + s["running"]

        lightest = min(self.engines, key=depth)
        if depth(affine) > depth(lightest) + self.engine.cfg.max_batch_size:
            return lightest
        return affine

    # ------------------------------------------------------------ metadata
    # Conservative cold-start tables (used until the engine has measured
    # enough of its own traffic to fit real ones).
    DEFAULT_TTFT_TABLE = [[128, 30.0], [512, 80.0], [2048, 250.0],
                          [4096, 520.0]]
    DEFAULT_TPOT_TABLE = [[1, 128, 6.0], [4, 2048, 9.0],
                          [8, 8192, 14.0], [16, 32768, 25.0]]

    def profiling_tables(self) -> tuple[list, list]:
        """SLO profiling tables from live engine telemetry, replacing the
        reference's offline-profiled tables (`common/types.h:207-210`).
        Samples are bucketed (median per bucket, robust to stragglers /
        compile spikes); until >= 3 distinct buckets exist the
        conservative defaults are advertised so the predictor always has
        something to fit."""
        import statistics

        ttft: dict[int, list[float]] = {}
        tpot: dict[int, list[tuple[int, float]]] = {}
        for eng in self.engines:
            for a in list(eng.telemetry.admissions):
                bucket = 1 << max(5, (a.prompt_len - 1).bit_length())
                ttft.setdefault(bucket, []).append(a.prefill_ms)
            for d in list(eng.telemetry.decodes):
                tpot.setdefault(d.live, []).append(
                    (d.context_tokens, d.ms_per_tok))
        ttft_table = [[b, statistics.median(v)]
                      for b, v in sorted(ttft.items())]
        tpot_table = [
            [b, statistics.median(t for t, _ in v),
             statistics.median(m for _, m in v)]
            for b, v in sorted(tpot.items())]
        if len(ttft_table) < 3:
            ttft_table = self.DEFAULT_TTFT_TABLE
        if len(tpot_table) < 3:
            tpot_table = self.DEFAULT_TPOT_TABLE
        return ttft_table, tpot_table

    def meta(self) -> InstanceMetaInfo:
        ecfg = self.engine.cfg
        mcfg = ecfg.model
        ttft_table, tpot_table = self.profiling_tables()
        return InstanceMetaInfo(
            name=self.name, rpc_address=self.name, type=self.instance_type,
            dp_size=len(self.engines),
            topology=TpuTopology(
                slice_id=self.cfg.slice_id,
                host=self.cfg.topo_host,
                chip=self.cfg.topo_chip,
                # Describes THIS engine's mesh (mesh-less = one device),
                # not the host's device count — the device-KV-transfer
                # gate compares these between peers.
                mesh_shape=self._mesh_shape(),
                axis_names=self._mesh_axes(),
                host_addrs=[self.name],
                kv_transfer_addr=self.kv_transfer.address
                if self.kv_transfer is not None else ""),
            kv_page_size=ecfg.page_size,
            kv_dtype=str(mcfg.dtype.__name__ if hasattr(mcfg.dtype, "__name__")
                         else mcfg.dtype),
            # The KV pool's layout (what a peer's pages must match): its
            # planes and the width a key is held at.
            num_layers=mcfg.kv_layers, num_kv_heads=mcfg.num_kv_heads,
            head_dim=mcfg.kv_head_dim,
            max_context_len=ecfg.max_seq_len,
            incarnation_id=self.incarnation_id,
            register_ts_ms=int(time.time() * 1000),
            models=[self.cfg.model_id],
            # Dispatch-wire negotiation: this build parses msgpack on the
            # enriched accept endpoints, making the hot wire symmetric
            # with the (already-msgpack) Generations return wire.
            wire_formats=[dispatch_wire.WIRE_MSGPACK,
                          dispatch_wire.WIRE_JSON],
            # Latency tables for the SLO predictor, fit from this engine's
            # own measured traffic (conservative defaults until warm) —
            # refreshed on every heartbeat re-registration so the
            # scheduler's predictor tracks the live instance.
            ttft_profiling_data=ttft_table,
            tpot_profiling_data=tpot_table,
        )

    # ------------------------------------------------------------ lifecycle
    def start(self) -> "EngineAgent":
        # Continuous profiler (profiling/sampler.py): refcounted — an
        # in-process agent sharing a master's process shares its sampler
        # (and its configure()d rate) instead of spawning a second one.
        PROFILER.start()
        self._profiler_started = True
        for eng in self.engines:
            eng.start()
        t = threading.Thread(target=self._run_server, daemon=True,
                             name=f"agent-http-{self.port}")
        t.start()
        self._threads.append(t)
        if not self._started.wait(30):
            raise RuntimeError("engine agent HTTP server failed to start")
        self.register()
        hb = threading.Thread(target=self._heartbeat_loop, daemon=True,
                              name="agent-heartbeat")
        hb.start()
        self._threads.append(hb)
        logger.info("engine agent %s (%s, model=%s) up",
                    self.name, self.instance_type.value, self.cfg.model_id)
        return self

    def register(self) -> None:
        meta = self.meta()
        meta.draining = self._draining
        self.coord.set(instance_key(self.instance_type.value, self.name),
                       meta.to_json(), ttl_s=self.cfg.lease_ttl_s)

    def drain(self, timeout_s: float = 60.0) -> None:
        """Graceful shutdown: advertise draining (the scheduler stops
        routing here on the next registration refresh), let in-flight
        requests finish, then stop. The reference has no drain — instances
        die abruptly and their requests are cancel-and-surfaced; this
        keeps live streams intact across planned restarts."""
        logger.info("agent %s draining (timeout %.0fs)", self.name,
                    timeout_s)
        self._draining = True
        self.register()
        # Grace window: requests the master routed just before the
        # draining flag landed may still be in HTTP flight (not yet in
        # engine stats) — an instant idle-stop would kill them.
        time.sleep(min(timeout_s / 4,
                       max(1.0, self.cfg.heartbeat_interval_s)))
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            stats = self.aggregate_stats()
            if stats["running"] == 0 and stats["waiting"] == 0:
                break
            time.sleep(0.2)
        self.stop()

    def stop(self) -> None:
        self._alive = False
        if self._profiler_started:
            self._profiler_started = False
            PROFILER.stop()
        RECORDER.remove_context_provider("engine", self._anomaly_context)
        self.coord.rm(instance_key(self.instance_type.value, self.name))
        self.streamer.stop()
        if self.kv_transfer is not None:
            self.kv_transfer.close()
        for eng in self.engines:
            eng.stop()
        if self._loop is not None and self._loop.is_running():
            self._loop.call_soon_threadsafe(self._loop.stop)
        self.coord.close()

    def _run_server(self) -> None:
        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)
        app = web.Application()
        app.router.add_post("/v1/completions", self._h_completion)
        app.router.add_post("/v1/chat/completions", self._h_chat)
        app.router.add_post("/v1/embeddings", self._h_embeddings)
        app.router.add_get("/v1/models", self._h_models)
        app.router.add_get("/health", self._h_health)
        app.router.add_get("/stats", self._h_stats)
        app.router.add_get("/metrics", self._h_metrics)
        # This agent process's view of a trace (engine-side spans; span
        # stores are per-process — the master serves the orchestration
        # legs under the same trace_id).
        app.router.add_get("/admin/trace", tracing.handle_admin_trace)
        app.router.add_get("/admin/trace/recent",
                           tracing.handle_admin_trace_recent)
        app.router.add_get("/admin/flightrecorder/recent",
                           flightrecorder.handle_flightrecorder_recent)
        app.router.add_get("/admin/profile", _handle_admin_profile)
        app.router.add_post("/rpc/link", self._h_link)
        app.router.add_post("/rpc/unlink", self._h_unlink)
        app.router.add_post("/rpc/cancel", self._h_cancel)
        app.router.add_post("/rpc/flip_role", self._h_flip)
        app.router.add_post("/rpc/drain", self._h_drain)
        app.router.add_post("/rpc/kv_transfer", self._h_kv_transfer)
        app.router.add_post("/rpc/kv_stream_pull", self._h_kv_stream_pull)
        app.router.add_post("/rpc/encode", self._h_encode)

        async def _start():
            self._runner = web.AppRunner(app)
            await self._runner.setup()
            site = web.TCPSite(self._runner, self.cfg.host, self.port)
            await site.start()

        self._loop.run_until_complete(_start())
        self._started.set()
        try:
            self._loop.run_forever()
        finally:
            self._loop.run_until_complete(self._runner.cleanup())
            self._loop.close()

    # ----------------------------------------------------------- heartbeats
    def _heartbeat_loop(self) -> None:
        while self._alive:
            time.sleep(self.cfg.heartbeat_interval_s)
            if not self._alive:
                return
            try:
                self.register()   # lease refresh via re-registration
                if self.kv_transfer is not None:
                    self.kv_transfer.gc()   # free never-pulled KV offers
                self.kv_stream.gc()         # ... and expired stream offers
                # Sharded telemetry (ISSUE 15): beats go to the OWNING
                # master under the rendezvous shard map, not the elected
                # master — the elected master's ingest funnel was the
                # next single-process ceiling. mode="master" keeps the
                # legacy funnel for mixed-version fleets.
                if self._telemetry_mode == "master":
                    target = self.coord.get(MASTER_KEY) or ""
                    if target:
                        self._last_master = target
                    elif self.cfg.degraded_mode != "off":
                        # Static stability: an unreachable plane
                        # resolves no master — keep beating at the last
                        # one that did (the owner path holds inside the
                        # resolver).
                        target = self._last_master
                else:
                    target = self.telemetry_owner()
                if not target:
                    continue
                stats = self.aggregate_stats()
                ev = self.engines[0].drain_kv_events()
                for eng in self.engines[1:]:
                    ev.merge(eng.drain_kv_events())
                # Atomic take-and-reset per engine: the old bare
                # read-then-zero raced the pump's read-max-write and
                # could drop the window's worst sample (the exact number
                # SLO routing keys off). Found by XLLM_STATE_DEBUG.
                drained = [e.drain_recent_latency() for e in self.engines]
                payload = {
                    "name": self.name,
                    "incarnation_id": self.incarnation_id,
                    "load_metrics": {
                        "waiting_requests_num": stats["waiting"],
                        "running_requests_num": stats["running"],
                        "hbm_cache_usage_perc": stats["kv_usage_perc"],
                    },
                    "latency_metrics": {
                        "recent_max_ttft": max(t for t, _ in drained),
                        "recent_max_tbt": max(t for _, t in drained),
                    },
                }
                if not self._post_heartbeat(target, payload, ev):
                    # Owner unreachable mid-stream: the resolver excludes
                    # it and the RENDEZVOUS SUCCESSOR gets this same beat
                    # immediately — the takeover must not wait a full
                    # interval or the new owner starts from silence.
                    self.telemetry_owner.note_failure(target)
                    successor = self.telemetry_owner() \
                        if self._telemetry_mode != "master" else ""
                    if successor and successor != target:
                        self._post_heartbeat(successor, payload, ev)
            except Exception:  # noqa: BLE001
                logger.exception("heartbeat failed")

    def _post_heartbeat(self, target: str, payload: dict,
                        ev) -> bool:
        """One heartbeat delivery. mode="mux": a tagged frame on the
        multiplexed telemetry session (shared keepalive connection with
        the delta pushes); a legacy target (404) demotes this agent to
        the per-endpoint wire. Legacy wire: msgpack with raw 16-byte
        KV-event keys, demoted to JSON per master on 400/415 (re-sent —
        heartbeat replay is idempotent: the index applies absolute tier
        moves)."""
        try:
            self._note_master(target)
            if self._telemetry_mode == "mux":
                payload = dict(payload)
                payload["kv_cache_event"] = ev.to_wire_dict()
                body, ctype = dispatch_wire.encode_telemetry(
                    [{"t": dispatch_wire.TELEMETRY_HB, "d": payload}])
                r = self.telemetry_session.post(
                    f"http://{target}/rpc/telemetry", data=body,
                    headers={"Content-Type": ctype}, timeout=3)
                ENGINE_HEARTBEATS_TOTAL.labels(master=target).inc()
                if r.status_code not in (404, 405):
                    if r.status_code == 200:
                        self._adopt_owner_hint(r, target)
                        return True
                    return False
                logger.warning("telemetry target %s lacks /rpc/telemetry; "
                               "demoting agent to the legacy elected-"
                               "master funnel", target)
                # A 404 means a PRE-sharding master: in that fleet only
                # the ELECTED master uploads load metrics from beats it
                # ingests locally, so "owner" routing would strand our
                # telemetry on a non-elected replica — go all the way
                # back to the reference funnel (review catch).
                self._telemetry_mode = "master"
            fmt = self._hb_wire
            payload = dict(payload)
            payload["kv_cache_event"] = (
                ev.to_wire_dict() if fmt == dispatch_wire.WIRE_MSGPACK
                else ev.to_dict())
            body, ctype = dispatch_wire.encode_dispatch(payload, fmt)
            r = self.telemetry_session.post(
                f"http://{target}/rpc/heartbeat", data=body,
                headers={"Content-Type": ctype}, timeout=3)
            ENGINE_HEARTBEATS_TOTAL.labels(master=target).inc()
            if r.status_code in (400, 415) \
                    and fmt == dispatch_wire.WIRE_MSGPACK:
                logger.warning(
                    "master rejected msgpack heartbeat (%d); demoting "
                    "to JSON wire", r.status_code)
                self._hb_wire = dispatch_wire.WIRE_JSON
                payload["kv_cache_event"] = ev.to_dict()
                body, ctype = dispatch_wire.encode_dispatch(
                    payload, dispatch_wire.WIRE_JSON)
                r = self.telemetry_session.post(
                    f"http://{target}/rpc/heartbeat", data=body,
                    headers={"Content-Type": ctype}, timeout=3)
            if r.status_code == 200:
                self._adopt_owner_hint(r, target)
                return True
            return False
        except _requests.RequestException as e:
            logger.warning("heartbeat to %s failed: %s", target, e)
            return False

    def _adopt_owner_hint(self, r, target: str) -> None:
        """Heartbeat responses carry the receiving master's view of our
        telemetry owner (`owner`): on a membership race our mirrored
        resolution can lag the masters' — adopting the hint re-routes
        the NEXT beat instead of waiting a resolver cache window out."""
        if self._telemetry_mode == "master":
            return
        try:
            owner = (r.json() or {}).get("owner", "")
        except ValueError:
            return
        if owner and owner != target:
            logger.info("telemetry owner hint: %s -> %s", target, owner)
            self.telemetry_owner.pin(owner)

    def _note_master(self, master: str) -> None:
        """Track the heartbeat destination master. On a change
        (election / failover): re-probe the msgpack wire (the new master
        may be a newer build than the one that demoted us) AND evict the
        old master's labeled heartbeat series — the master address is
        ephemeral (host:port), so a long-lived engine that outlives many
        masters would otherwise grow /metrics one dead series per
        election (the agent-side mirror of instance_mgr's
        evicted-instance series eviction)."""
        if master == self._hb_master:
            return
        if self._hb_master:
            evict_series(ENGINE_HEARTBEATS_TOTAL, master=self._hb_master)
        # A flap back to a previously-evicted master legitimately
        # re-creates its series (not the stale-writer resurrection bug).
        _lifecycle.note_series_revived(master)
        self._hb_master = master
        self._hb_wire = dispatch_wire.WIRE_MSGPACK

    # ------------------------------------------------------------ handlers
    def aggregate_stats(self) -> dict[str, Any]:
        """Instance-level stats = sum over replicas (kv usage: max — the
        scheduler treats it as a saturation signal)."""
        per = [e.stats() for e in self.engines]
        return {
            "waiting": sum(s["waiting"] for s in per),
            "running": sum(s["running"] for s in per),
            "kv_usage_perc": max(s["kv_usage_perc"] for s in per),
            "cached_blocks": sum(s["cached_blocks"] for s in per),
            "total_generated": sum(s["total_generated"] for s in per),
            "dp_size": len(self.engines),
            "sarathi_rides": sum(e.telemetry.counters["sarathi_rides"]
                                 for e in self.engines),
            "attention_paths": [s.get("attention_paths", {}) for s in per],
            "look_ahead": [s.get("look_ahead") for s in per],
        }

    async def _h_health(self, req: web.Request) -> web.Response:
        return web.json_response({"status": "ok"})

    def telemetry_stats(self) -> dict[str, Any]:
        """Connection accounting for the multiplexed telemetry session —
        the bench's O(engines) fan-out evidence (hosts = distinct master
        pools this engine currently holds; mux mode keeps it at 1)."""
        from ..rpc.channel import session_connection_stats

        return {
            "mode": self._telemetry_mode,
            "owner": self.telemetry_owner() or "",
            "mux_sends": self.streamer.mux_sends,
            "direct_sends": self.streamer.direct_sends,
            **session_connection_stats(self.telemetry_session),
        }

    async def _h_stats(self, req: web.Request) -> web.Response:
        return web.json_response({
            **self.aggregate_stats(),
            "devices": [e.device_report() for e in self.engines],
            "telemetry": self.telemetry_stats(),
            "kv_transfer": {
                "device_sent": self.kv_device_sent,
                "host_sent": self.kv_host_sent,
                "device_received": self.kv_device_received,
                "host_received": self.kv_host_received,
                "stream_sent": self.kv_stream_sent,
                "stream_received": self.kv_stream_received,
                "bandwidth": self.bandwidth.stats(),
            },
            "kv_tier": self._tier_stats(),
            "ttft_spans": self._span_summary(),
            "engine_trace": self.engine_trace(),
        })

    def _tier_stats(self) -> dict[str, Any]:
        """Summed tier-store telemetry across replicas ({} = tiering
        off)."""
        out: dict[str, Any] = {}
        for eng in self.engines:
            store = getattr(eng, "tier_store", None)
            if store is None:
                continue
            for k, v in store.stats().items():
                out[k] = out.get(k, 0) + v if k != "block_nbytes" else v
        return out

    def _span_summary(self) -> dict[str, float]:
        """p50s of the TTFT span samples (agent accept -> first delta;
        engine queue wait; prefill execution) so an external bench can
        attribute client TTFT across process boundaries."""
        def p50(xs):
            xs = sorted(xs)
            return round(xs[len(xs) // 2], 1) if xs else 0.0

        eng = [a for e in self.engines for a in list(e.telemetry.admissions)
               if a.queue_ms is not None]
        return {
            "n": len(self.ttft_spans),
            "agent_accept_to_first_delta_ms": p50(list(self.ttft_spans)),
            "engine_queue_ms": p50([a.queue_ms for a in eng]),
            "engine_prefill_ms": p50([a.prefill_ms for a in eng]),
        }

    def engine_trace(self) -> dict[str, Any]:
        """The engines' step-loop telemetry (engine/telemetry.py), summed
        over replicas: totals since boot, and the last 30 s."""
        return summarize_telemetry(e.telemetry for e in self.engines)

    async def _h_metrics(self, req: web.Request) -> web.Response:
        """Prometheus text exposition of engine state (the service's
        /metrics covers the orchestration plane; this covers the chip)."""
        st = self.aggregate_stats()
        lines = [
            "# TYPE engine_waiting_requests gauge",
            f"engine_waiting_requests {st['waiting']}",
            "# TYPE engine_running_requests gauge",
            f"engine_running_requests {st['running']}",
            "# TYPE engine_kv_usage_perc gauge",
            f"engine_kv_usage_perc {st['kv_usage_perc']:.6f}",
            "# TYPE engine_cached_prefix_blocks gauge",
            f"engine_cached_prefix_blocks {st['cached_blocks']}",
            "# TYPE engine_generated_tokens_total counter",
            f"engine_generated_tokens_total {st['total_generated']}",
            "# TYPE engine_recent_max_ttft_milliseconds gauge",
            f"engine_recent_max_ttft_milliseconds "
            f"{max(e.recent_max_ttft_ms for e in self.engines):.3f}",
            "# TYPE engine_recent_max_tbt_milliseconds gauge",
            f"engine_recent_max_tbt_milliseconds "
            f"{max(e.recent_max_tbt_ms for e in self.engines):.3f}",
            "# TYPE engine_dp_size gauge",
            f"engine_dp_size {len(self.engines)}",
        ]
        # The step loop's counters (engine_preemptions_total and
        # engine_sarathi_rides_total among them), by-key families labeled.
        labels = {"prefill_calls": "bucket", "decode_calls": "horizon",
                  "admissions_blocked": "reason", "host_s": "phase",
                  "look_ahead_late": "outcome"}
        for name, v in self.engine_trace()["total"].items():
            metric = ("engine_host_seconds_total" if name == "host_s"
                      else f"engine_{name}_total")
            lines.append(f"# TYPE {metric} counter")
            if isinstance(v, dict):
                lines += [f'{metric}{{{labels[name]}="{k}"}} {x}'
                          for k, x in sorted(v.items())]
            else:
                lines.append(f"{metric} {v}")
        tel = self.telemetry_stats()
        lines += [
            "# TYPE engine_telemetry_session_hosts gauge",
            f"engine_telemetry_session_hosts {tel['hosts']}",
            "# TYPE engine_telemetry_connections_created counter",
            f"engine_telemetry_connections_created "
            f"{tel['connections_created']}",
            "# TYPE engine_telemetry_mux_sends_total counter",
            f"engine_telemetry_mux_sends_total {tel['mux_sends']}",
            "# TYPE engine_telemetry_direct_sends_total counter",
            f"engine_telemetry_direct_sends_total {tel['direct_sends']}",
        ]
        tier = self._tier_stats()
        if tier:
            lines += [
                "# TYPE engine_kv_tier_blocks gauge",
                f'engine_kv_tier_blocks{{tier="dram"}} '
                f"{tier.get('dram_blocks', 0)}",
                f'engine_kv_tier_blocks{{tier="ssd"}} '
                f"{tier.get('ssd_blocks', 0)}",
                "# TYPE engine_kv_tier_offloads_total counter",
                f"engine_kv_tier_offloads_total "
                f"{tier.get('offload_total', 0)}",
                "# TYPE engine_kv_tier_onloads_total counter",
                f"engine_kv_tier_onloads_total "
                f"{tier.get('onload_total', 0)}",
                "# TYPE engine_kv_tier_bytes_total counter",
                f'engine_kv_tier_bytes_total{{direction="offload"}} '
                f"{tier.get('bytes_offloaded', 0)}",
                f'engine_kv_tier_bytes_total{{direction="onload"}} '
                f"{tier.get('bytes_onloaded', 0)}",
            ]
        for link, bw in self.bandwidth.stats().items():
            lines += [
                f'engine_kv_stream_bytes_total{{link="{link}"}} '
                f"{bw['bytes_total']:.0f}",
                f'engine_kv_stream_throughput_bytes_per_s{{link="{link}"}} '
                f"{bw['throughput_bytes_per_s']:.1f}",
            ]
        spans = self._span_summary()
        lines += [
            "# TYPE engine_ttft_span_p50_milliseconds gauge",
            'engine_ttft_span_p50_milliseconds{span="agent_total"} '
            f"{spans['agent_accept_to_first_delta_ms']:.3f}",
            'engine_ttft_span_p50_milliseconds{span="engine_queue"} '
            f"{spans['engine_queue_ms']:.3f}",
            'engine_ttft_span_p50_milliseconds{span="engine_prefill"} '
            f"{spans['engine_prefill_ms']:.3f}",
        ]
        # Agent-side labeled series (common/metrics.py instruments; only
        # the agent-owned families render here — evicted on unlink /
        # master change so the exposition stays bounded).
        for inst in (ENGINE_PEER_LINKED, ENGINE_HEARTBEATS_TOTAL):
            rendered = inst.render()
            if rendered:
                lines.append(f"# TYPE {inst.name} {inst.kind}")
                lines.append(rendered.rstrip("\n"))
        return web.Response(text="\n".join(lines) + "\n",
                            content_type="text/plain")

    def _stage_span(self, point: str, ctx: Optional[TraceContext],
                    sid: str, **attrs: Any):
        """Engine-side stage span, parented under the orchestrator's
        carried context. Standalone requests (no context) are not traced —
        there is no tree to hang them on."""
        return TRACER.start_span(point, ctx=ctx, request_id=sid,  # xlint: allow-span-point(forwards literal point names from its call sites)
                                 require_ctx=True, instance=self.name,
                                 incarnation=self.incarnation_id, **attrs)

    async def _h_models(self, req: web.Request) -> web.Response:
        return web.json_response({"object": "list", "data": [
            {"id": self.cfg.model_id, "object": "model"}]})

    async def _h_link(self, req: web.Request) -> web.Response:
        body = await req.json()
        peer = InstanceMetaInfo.from_json(json.dumps(body.get("peer", {})))
        # KV-layout compatibility gate (replaces the reference's opaque
        # k/v_cache_ids handshake with an explicit contract check).
        mine = self.meta()
        for f in ("kv_page_size", "num_layers", "num_kv_heads", "head_dim"):
            if getattr(peer, f) and getattr(peer, f) != getattr(mine, f):
                return web.json_response(
                    {"ok": False,
                     "error": f"kv layout mismatch on {f}"}, status=409)
        self.linked_peers[peer.name] = peer
        # Unlink→relink of the same peer re-creates its series on purpose.
        _lifecycle.note_series_revived(peer.name)
        ENGINE_PEER_LINKED.labels(peer=peer.name).set(1)
        return web.json_response({"ok": True})

    async def _h_unlink(self, req: web.Request) -> web.Response:
        body = await req.json()
        peer_name = body.get("peer_name", "")
        if self.linked_peers.pop(peer_name, None) is not None:
            # PD link torn down: evict the peer's labeled series, or a
            # long-lived engine's /metrics grows one dead series per
            # departed peer (ephemeral ports make the set unbounded).
            evict_series(ENGINE_PEER_LINKED, peer=peer_name)
        return web.json_response({"ok": True})

    async def _h_cancel(self, req: web.Request) -> web.Response:
        body = await req.json()
        self.cancel(body.get("service_request_id", ""))
        return web.json_response({"ok": True})

    async def _h_drain(self, req: web.Request) -> web.Response:
        """Master-initiated graceful retirement (the autoscaler's
        scale-in path): run the existing drain sequence — advertise
        `draining`, wait for in-flight work, stop — on a background
        thread; the RPC acks immediately so the controller's reconcile
        pass never blocks on an engine's drain window."""
        if not self._draining:
            threading.Thread(target=self.drain, name="agent-drain",
                             daemon=True).start()
        return web.json_response({"ok": True, "draining": True})

    async def _h_flip(self, req: web.Request) -> web.Response:
        """Dynamic PD-role switch (reference `instance_mgr.cpp:1023-1063`).
        The engine keeps its weights + KV pool; only the advertised role (and
        hence the traffic mix routed here) changes."""
        body = await req.json()
        new_type = InstanceType.parse(body.get("type"))
        old_key = instance_key(self.instance_type.value, self.name)
        self.instance_type = new_type

        def _reregister():
            # Coordination I/O is blocking (requests-backed client) — off
            # the event loop, or a slow coordination server stalls every
            # in-flight stream on this agent (found by xlint's
            # async-blocking rule).
            self.coord.rm(old_key)
            self.register()

        await asyncio.to_thread(_reregister)
        return web.json_response({"ok": True})

    async def _h_embeddings(self, req: web.Request) -> web.Response:
        """OpenAI embeddings over the engine's embed forward (the
        reference stubs this endpoint as "not support",
        `http_service/service.cpp:500-517`)."""
        try:
            body = await req.json()
        except json.JSONDecodeError:
            return web.json_response({"error": "invalid JSON"}, status=400)
        inputs = body.get("input")
        if isinstance(inputs, str):
            inputs = [inputs]
        if not isinstance(inputs, list) or not inputs:
            return web.json_response(
                {"error": "input must be a string or list of strings"},
                status=400)
        if self.engine.family.embed_forward is None:
            return web.json_response(
                {"error": f"model family {self.engine.cfg.model_family} "
                          "has no embedding forward"}, status=501)
        max_len = self.engine.cfg.max_seq_len

        def _encode_and_embed():
            # Off the event loop: tokenizing a big batch (OpenAI allows
            # thousands of inputs) must not stall in-flight SSE streams.
            tok = self.engine.tokenizer
            token_lists = [tok.encode(str(t))[:max_len] or [0]
                           for t in inputs]
            eng = self._pick_engine(token_lists[0])
            return eng.embed(token_lists), token_lists

        vecs, token_lists = await asyncio.get_running_loop() \
            .run_in_executor(None, _encode_and_embed)
        n_tokens = sum(len(t) for t in token_lists)
        return web.json_response({
            "object": "list",
            "model": body.get("model", self.cfg.model_id),
            "data": [{"object": "embedding", "index": i,
                      "embedding": [float(x) for x in v]}
                     for i, v in enumerate(vecs)],
            "usage": {"prompt_tokens": n_tokens, "total_tokens": n_tokens},
        })

    async def _h_completion(self, req: web.Request) -> web.Response:
        return await self._accept(req, chat=False)

    async def _h_chat(self, req: web.Request) -> web.Response:
        return await self._accept(req, chat=True)

    async def _accept(self, req: web.Request, chat: bool) -> web.Response:
        t_recv = time.monotonic()
        try:
            # Negotiated dispatch wire: msgpack (current masters) or JSON
            # (legacy masters, direct curl) by Content-Type.
            body = dispatch_wire.decode_body(req.content_type,
                                             await req.read())
        except ValueError:
            return web.json_response({"error": "invalid request body"},
                                     status=400)
        if not isinstance(body, dict):
            return web.json_response({"error": "body must be an object"},
                                     status=400)
        sid = body.get("service_request_id") or f"local-{uuid.uuid4().hex[:8]}"
        source = body.get("source_service_addr", "")
        token_ids = list(body.get("token_ids") or ())
        # End-to-end deadline (overload plane): the enriched payload
        # carries the ABSOLUTE deadline; work that expired while queued
        # upstream is refused outright, and a mid-decode expiry cancels
        # the engine stream within one output callback.
        deadline_ms = int(body.get("deadline_ms") or 0)
        if deadline_ms and now_ms() > deadline_ms:
            return web.json_response({"error": "deadline exceeded"},
                                     status=504)

        # EPD multimodal: extract images, encode (locally or on the routed
        # ENCODE instance), and rebuild token ids with image-token runs the
        # model splices embeddings into (BASELINE config 5).
        mm_embeds = None
        if chat and self.engine.cfg.model_family == "qwen2_vl":
            try:
                pixels = self._extract_images(body.get("messages") or [])
            except ValueError as e:
                return web.json_response({"error": str(e)}, status=400)
            except Exception as e:  # noqa: BLE001  # xlint: allow-broad-except(bad base64/PIL data is surfaced as a 400 to the client)
                return web.json_response(
                    {"error": f"invalid image payload: {e}"}, status=400)
            if pixels is not None:
                encode_name = (body.get("routing") or {}).get(
                    "encode_name", "")
                try:
                    mm_embeds = await asyncio.get_running_loop() \
                        .run_in_executor(None, self._encode_pixels, pixels,
                                         encode_name)
                except Exception as e:  # noqa: BLE001  # xlint: allow-broad-except(encode failure is surfaced as a 502 to the client)
                    return web.json_response(
                        {"error": f"vision encode failed: {e}"}, status=502)
                token_ids = self._build_mm_token_ids(
                    body.get("messages") or [])
        if not token_ids:
            # Standalone mode (no orchestrator enrichment): tokenize here.
            prompt = body.get("prompt", "")
            if chat and not prompt:
                msgs = body.get("messages") or []
                prompt = "\n".join(str(m.get("content", "")) for m in msgs)
            token_ids = self.engine.tokenizer.encode(str(prompt))
        sampling = self._sampling_from_body(body)

        if not source:
            return web.json_response(
                {"error": "source_service_addr required (engine streams "
                          "results to the service RPC endpoint)"}, status=400)

        dest = source
        first_delta = [True]
        # Trace propagation: stage spans parent under the orchestrator's
        # context carried in the enriched body. The prefill span opens at
        # accept and closes at the first delta (or the PD handoff); decode
        # runs from there to the terminal delta.
        ctx = TraceContext.from_dict(body.get("trace_context")) \
            or TraceContext.from_headers(req.headers)
        stage = {"span": self._stage_span("engine.prefill", ctx, sid,
                                          prompt_tokens=len(token_ids))}

        def end_prefill_span(err: Optional[str] = None) -> None:
            # What the engine's admission found (prefix_hit_tokens, bucket,
            # queue_ms): /admin/trace says whether the cache served THIS
            # request.
            a = getattr(stage.get("req"), "admission", None)
            if a is not None:
                stage["span"].set(prompt_tokens=a.prompt_len,
                                  prefix_hit_tokens=a.matched,
                                  bucket=a.bucket, queue_ms=a.queue_ms)
            stage["span"].end(err)

        def on_output(out: RequestOutput) -> None:
            # Agent-side TTFT span: HTTP accept -> first delta pushed to
            # the streamer. Client TTFT minus this is master+wire cost.
            if deadline_ms and not out.finished and now_ms() > deadline_ms:
                # Mid-decode deadline expiry: stop this request through
                # the existing cancel path (fans across dp replicas) —
                # token production halts within one pump interval. The
                # delta in hand still ships; the service 504s the
                # client either way.
                self.cancel(out.service_request_id)
            err = None if out.status.ok() else \
                f"ERROR: {out.status.message or out.status.code.name}"
            if first_delta[0]:
                first_delta[0] = False
                self.ttft_spans.append(
                    (time.monotonic() - t_recv) * 1000)
                end_prefill_span(err)
                # A failed prefill (error surfaced before any token) has
                # no decode stage — don't fabricate one.
                stage["span"] = NOOP_SPAN if err else \
                    self._stage_span("engine.decode", ctx, sid)
            if out.finished:
                stage["span"].end(err)
            self.streamer.push(dest, out)

        # PD disaggregation: a PREFILL-role instance with a routed decode
        # peer prefills, then ships KV + first token to the peer, which owns
        # the stream from there (reference PD pipeline, SURVEY.md §2.12).
        decode_name = (body.get("routing") or {}).get("decode_name", "")
        if self.instance_type == InstanceType.PREFILL and decode_name \
                and decode_name != self.name:

            def on_prefill_done(h: PrefillHandoff,
                                _peer: str = decode_name,
                                _dest: str = dest) -> None:
                end_prefill_span()
                threading.Thread(
                    target=self._transfer_to_peer, daemon=True,
                    args=(h, _peer, _dest, ctx),
                    name=f"kv-transfer-{h.service_request_id}").start()

            stage["req"] = EngineRequest(
                service_request_id=sid,
                request_id=body.get("request_id", sid),
                token_ids=token_ids, sampling=sampling,
                mm_embeds=mm_embeds,
                prefill_only=True, on_prefill_done=on_prefill_done,
                on_output=on_output)   # surfaces prefill-side errors
            self._pick_engine(token_ids).submit(stage["req"])
            return web.json_response({"ok": True,
                                      "service_request_id": sid})

        # n > 1: fan out into n engine sequences sharing the prompt (the
        # prefix cache dedupes their prefill); choice k's outputs are
        # re-indexed, and `finished` is withheld until every choice is done
        # (the service closes the stream on the first finished delta).
        n = max(1, sampling.n)
        engine = self._pick_engine(token_ids)
        if n == 1:
            stage["req"] = EngineRequest(
                service_request_id=sid,
                request_id=body.get("request_id", sid),
                token_ids=token_ids, sampling=sampling, on_output=on_output,
                mm_embeds=mm_embeds,
                offline=bool(body.get("offline", False)),
                priority=int(body.get("priority") or 0))
            engine.submit(stage["req"])
            return web.json_response({"ok": True, "service_request_id": sid})

        # All n choices go to ONE replica so its prefix cache dedupes the
        # shared prompt prefill. Stage spans don't model the n-way fan-out;
        # close the prefill span here so the trace still records admission.
        stage["span"].set(n=n).end()
        agg = _ChoiceAggregator(n, lambda out: self.streamer.push(dest, out))
        for k in range(n):
            sub_sampling = sampling
            if sampling.seed is not None:
                sub_sampling = SamplingParams.from_dict(sampling.to_dict())
                sub_sampling.seed = sampling.seed + k
            engine.submit(EngineRequest(
                service_request_id=sid,
                request_id=body.get("request_id", sid),
                token_ids=list(token_ids), sampling=sub_sampling,
                on_output=agg.callback_for(k),
                mm_embeds=mm_embeds,
                offline=bool(body.get("offline", False)),
                priority=int(body.get("priority") or 0)))
        return web.json_response({"ok": True, "service_request_id": sid})

    def _transfer_to_peer(self, h: PrefillHandoff, peer: str, dest: str,
                          ctx: Optional[TraceContext] = None) -> None:
        """Ship a prefilled sequence to its decode peer. Device path first
        (KV pulled device-to-device via the peer's transfer connection —
        ICI within a slice, DCN fabric across), host-msgpack fallback
        behind the same PrefillHandoff contract."""
        trace_dict = ctx.to_dict() if ctx is not None else None
        peer_meta = self.linked_peers.get(peer)
        if (self.kv_transfer is not None and peer_meta is not None
                and peer_meta.topology.kv_transfer_addr
                and self._same_mesh_topology(peer_meta)):
            desc = None
            try:
                desc = self.kv_transfer.offer(
                    h.service_request_id, h.kv_blob, self.incarnation_id,
                    ctx=ctx)
                self._post_handoff(peer, pack_handoff(
                    h, dest, kv_ref=desc, source_instance=self.name,
                    trace_context=trace_dict))
                self.kv_transfer.release(desc["uuid"])
                self.kv_device_sent += 1
                return
            except Exception as e:  # noqa: BLE001
                if desc is not None:
                    self.kv_transfer.release(desc["uuid"])
                logger.warning(
                    "device KV transfer of %s to %s failed (%s); falling "
                    "back to host path", h.service_request_id, peer, e)
        # Streaming host path: big payloads are offered for chunked pull
        # (many blocks per round-trip, bandwidth-accounted) instead of
        # being carried inline in one monolithic POST.
        blob_np = None
        thresh = self.cfg.kv_stream_threshold_bytes
        if thresh >= 0:
            try:
                blob_np = np.asarray(h.kv_blob)
            except Exception:  # noqa: BLE001  # xlint: allow-broad-except(an invalidated/donated device buffer downgrades to the inline path, which re-fetches)
                blob_np = None
        if blob_np is not None and blob_np.nbytes >= thresh:
            desc = None
            try:
                desc = self.kv_stream.offer(
                    h.service_request_id, blob_np.tobytes(),
                    shape=list(blob_np.shape), dtype=str(blob_np.dtype),
                    incarnation=self.incarnation_id,
                    block_bytes=blob_np.nbytes
                    // max(1, blob_np.shape[2]), ctx=ctx)
                self._post_handoff(peer, pack_handoff(
                    h, dest, source_instance=self.name,
                    trace_context=trace_dict, kv_stream=desc))
                self.kv_stream.release(desc["stream_uuid"])
                self.kv_stream_sent += 1
                self.kv_host_sent += 1
                return
            except Exception as e:  # noqa: BLE001
                if desc is not None:
                    self.kv_stream.release(desc["stream_uuid"])
                logger.warning(
                    "streamed KV transfer of %s to %s failed (%s); "
                    "falling back to inline host path",
                    h.service_request_id, peer, e)
                # Stream fallback is an anomaly worth a post-mortem: the
                # handoff survives (inline path below), but bandwidth
                # pacing and chunked-pull benefits were lost mid-request.
                trace_id = ctx.trace_id if ctx is not None else ""
                TRACER.keep_trace(trace_id)
                RECORDER.record(
                    "kv_stream_fallback",
                    request_id=h.service_request_id, trace_id=trace_id,
                    detail={"peer": peer, "error": str(e),
                            "bytes": int(blob_np.nbytes)})
        try:
            with TRACER.span("kv_transfer.offer", ctx=ctx, require_ctx=True,
                             request_id=h.service_request_id,
                             instance=self.name, path="host"):
                self._post_handoff(peer, pack_handoff(
                    h, dest, source_instance=self.name,
                    trace_context=trace_dict))
            self.kv_host_sent += 1
        except Exception as e:  # noqa: BLE001
            logger.warning("KV transfer of %s to %s failed: %s",
                           h.service_request_id, peer, e)
            self.streamer.push(dest, RequestOutput(
                service_request_id=h.service_request_id,
                request_id=h.request_id,
                status=Status(StatusCode.UNAVAILABLE,
                              f"KV transfer to decode peer failed: {e}"),
                finished=True))

    def _mesh_shape(self) -> list[int]:
        return list(self.engine.mesh.devices.shape) \
            if self.engine.mesh else [1]

    def _mesh_axes(self) -> list[str]:
        return list(self.engine.mesh.axis_names) \
            if self.engine.mesh else ["data"]

    def _same_mesh_topology(self, peer_meta: InstanceMetaInfo) -> bool:
        """Sharded device pulls reconstruct the sender's partition spec on
        the receiver's mesh — shard layouts must match, so the device path
        requires an identical mesh topology on both ends. Mismatched pairs
        (or sharded->unsharded) fall back to the host path, which
        re-materializes on the receiver however it likes. (Cheap field
        reads — this runs on every handoff.)"""
        theirs = peer_meta.topology
        return (self._mesh_shape() == theirs.mesh_shape
                and self._mesh_axes() == theirs.axis_names)

    @staticmethod
    def _post_handoff(peer: str, payload: bytes) -> None:
        r = _requests.post(f"http://{peer}/rpc/kv_transfer",
                           data=payload,
                           headers={"Content-Type": "application/msgpack"},
                           timeout=60)
        if r.status_code != 200:
            raise RuntimeError(f"peer returned {r.status_code}: "
                               f"{r.text[:200]}")

    async def _h_encode(self, req: web.Request) -> web.Response:
        """EPD ENCODE stage: run the vision encoder on pixel arrays and
        return visual embeddings (msgpack). The reference claims EPD with no
        service mechanism (README.md:47); this endpoint + InstanceType.ENCODE
        define the contract: encode instances pin vision-encoder FLOPs to
        dedicated chips so they never contend with prefill/decode."""
        fam = self.engine.family
        encode_fn = None
        try:
            from ..models import qwen2_vl as _vl

            if self.engine.cfg.model_family == "qwen2_vl":
                encode_fn = _vl.encode_images
        except ImportError:
            pass
        if encode_fn is None:
            return web.json_response(
                {"error": f"model family {self.engine.cfg.model_family} "
                          "has no vision encoder"}, status=400)
        data = await req.read()
        obj = msgpack.unpackb(data, raw=False)
        self.encode_count += 1
        pixels = np.frombuffer(obj["bytes"], dtype=np.dtype(obj["dtype"])) \
            .reshape(obj["shape"])

        def _run_encoder() -> np.ndarray:
            # Off the event loop: first call may hit a multi-second XLA
            # compile, which must not freeze health probes / link RPCs.
            import jax.numpy as jnp

            embeds = encode_fn(self.engine.params, self.engine.cfg.model,
                               jnp.asarray(pixels))
            return np.asarray(embeds.astype(jnp.float32))

        embeds_np = await asyncio.get_running_loop().run_in_executor(
            None, _run_encoder)
        return web.Response(body=msgpack.packb({
            "bytes": embeds_np.tobytes(),
            "shape": list(embeds_np.shape),
            "dtype": "float32"}, use_bin_type=True),
            content_type="application/msgpack")

    async def _h_kv_stream_pull(self, req: web.Request) -> web.Response:
        """Serve one chunk of a streamed KV offer (msgpack in/out). The
        peer drives offsets; a chunk read is one memoryview slice — no
        per-frame re-serialization of the whole payload."""
        try:
            obj = msgpack.unpackb(await req.read(), raw=False)
            frame = self.kv_stream.read_chunk(
                int(obj["uuid"]), int(obj.get("offset", 0)),
                int(obj.get("max_bytes", self.cfg.kv_stream_chunk_bytes)))
        except Exception as e:  # noqa: BLE001  # xlint: allow-broad-except(malformed pull frame is surfaced as a 400 to the peer)
            return web.json_response({"error": f"bad pull frame: {e}"},
                                     status=400)
        if frame is None:
            return web.json_response({"error": "unknown or expired offer"},
                                     status=404)
        return web.Response(body=msgpack.packb(frame, use_bin_type=True),
                            content_type="application/msgpack")

    def _link_class(self, peer_name: str) -> str:
        """ICI-shaped vs DCN-shaped for bandwidth budgeting, derived from
        the topology coordinates via the shared link-cost kernel
        (common/topology.py). The accountant has two budget classes, so
        kernel "local" (same host — never leaves the machine) rides the
        ICI bucket. Peers without placement coordinates keep the legacy
        rule: same declared slice = ICI."""
        meta = self.linked_peers.get(peer_name)
        peer_topo = meta.topology if meta is not None else None
        if self.cfg.topo_host and getattr(peer_topo, "host", ""):
            mine = topo.Coord(self.cfg.slice_id, self.cfg.topo_host,
                              self.cfg.topo_chip, placed=True)
            link = topo.link_class(
                mine, topo.effective_coord(peer_topo, peer_name))
            return "ici" if link == topo.LINK_LOCAL else link
        if peer_topo is not None and peer_topo.slice_id \
                and peer_topo.slice_id == self.cfg.slice_id:
            return "ici"
        return "dcn"

    async def _h_kv_transfer(self, req: web.Request) -> web.Response:
        """Decode side of the PD handoff: accept prompt KV + first token,
        inject into the local decode batch. KV arrives either inline
        (host/DCN msgpack path) or as a `kv_ref` descriptor this side pulls
        device-to-device from the prefill peer's transfer server."""
        data = await req.read()
        try:
            obj = unpack_handoff(data)
        except Exception as e:  # noqa: BLE001  # xlint: allow-broad-except(malformed handoff is surfaced as a 400 to the peer)
            return web.json_response({"error": f"bad handoff: {e}"},
                                     status=400)
        # Enforce the P-D link on the transfer itself (the link-time
        # KV-layout gate protects nothing if any peer can push a handoff;
        # reference analog: transfers ride endpoints negotiated by Link
        # ops, `instance_mgr.cpp:1087-1113`).
        src = obj.get("source_instance", "")
        if src not in self.linked_peers:
            return web.json_response(
                {"error": f"instance {src or '<unknown>'} is not a linked "
                          "peer; rejecting KV handoff"}, status=403)
        sid = obj.get("service_request_id", "")
        ctx = TraceContext.from_dict(obj.get("trace_context"))
        now = time.monotonic()
        for k, ts in list(self._handoffs_seen.items()):
            if now - ts > 600:
                self._handoffs_seen.pop(k, None)
        if sid in self._handoffs_seen:
            # Duplicate delivery (prefill retried after a lost response):
            # the sequence is already injected — ack, don't re-inject.
            return web.json_response({"ok": True, "duplicate": True})
        # NOTE: sid is marked seen only once the payload is IN HAND (below,
        # after any pull awaits). Marking before a pull would bounce the
        # sender's inline retry as "duplicate" while the pull it raced can
        # still fail — the request would be lost with both sides reporting
        # success.
        if "kv_blob" not in obj and obj.get("kv_stream") is not None:
            # Streaming host path: pull the payload back in chunked
            # frames (executor thread — round-trips + pacing sleeps must
            # not stall the event loop), link-classed ICI vs DCN by the
            # peer's slice for bandwidth accounting.
            from .kv_transfer import pull_stream

            desc = obj["kv_stream"]
            link = self._link_class(src)
            try:
                obj["kv_blob"] = await asyncio.get_running_loop() \
                    .run_in_executor(
                        None, lambda: pull_stream(
                            src, desc, accountant=self.bandwidth,
                            link=link, ctx=ctx))
                # (the kv_blob else-branch below counts the host receive)
                self.kv_stream_received += 1
            except Exception as e:  # noqa: BLE001
                logger.warning("streamed KV pull for %s failed: %s",
                               sid, e)
                return web.json_response(
                    {"error": f"streamed KV pull failed: {e}"}, status=502)
        if "kv_blob" not in obj:
            ref = obj.get("kv_ref")
            if ref is None or self.kv_transfer is None:
                return web.json_response(
                    {"error": "no KV payload and no device-transfer "
                              "capability"}, status=400)
            try:
                # Off the event loop: the pull blocks on the device fabric.
                obj["kv_blob"] = await asyncio.get_running_loop() \
                    .run_in_executor(
                        None, lambda: self.kv_transfer.pull(ref, ctx=ctx))
                self.kv_device_received += 1
            except Exception as e:  # noqa: BLE001
                logger.warning("device KV pull for %s failed: %s",
                               obj.get("service_request_id"), e)
                return web.json_response(
                    {"error": f"device KV pull failed: {e}"}, status=502)
        else:
            self.kv_host_received += 1
        if sid in self._handoffs_seen:
            # An inline retry (sender gave up on the pull we were running)
            # interleaved on the event loop and already injected — this
            # incarnation of the payload is the duplicate.
            return web.json_response({"ok": True, "duplicate": True})
        # No await between this mark and submit() below: on the single
        # event loop the mark+inject pair is atomic wrt other deliveries.
        self._handoffs_seen[sid] = time.monotonic()
        dest = obj.get("source_service_addr", "")
        lp_d = obj.get("first_logprob")
        lp = None
        if lp_d:
            from ..common.request import LogProbData

            lp = LogProb(token=lp_d["token"], token_id=lp_d["token_id"],
                         logprob=lp_d["logprob"],
                         top_logprobs=[LogProbData(t[0], t[1], t[2])
                                       for t in lp_d.get("top", ())])

        dspan = self._stage_span("engine.decode", ctx, sid, injected=True)

        def on_output(out: RequestOutput) -> None:
            if out.finished:
                dspan.end()
            self.streamer.push(dest, out)

        self._pick_engine(list(obj["token_ids"])).submit(EngineRequest(
            service_request_id=obj["service_request_id"],
            request_id=obj.get("request_id", ""),
            token_ids=list(obj["token_ids"]),
            sampling=SamplingParams.from_dict(obj.get("sampling", {})),
            injected_first_token=int(obj["first_token"]),
            injected_kv=obj["kv_blob"],
            injected_first_logprob=lp,
            on_output=on_output))
        return web.json_response({"ok": True})

    # ------------------------------------------------------- multimodal
    @staticmethod
    def _is_image_part(part: Any) -> bool:
        """Single predicate shared by extraction and token building (the
        service's routing check uses the same startswith rule) — the two
        sides MUST agree or placeholder runs and embeddings mis-align."""
        return isinstance(part, dict) and \
            str(part.get("type", "")).startswith("image")

    def _extract_images(self, messages: list[dict]) -> Optional[np.ndarray]:
        """Collect image parts from chat messages as [N, S, S, 3] float32
        (S = the vision encoder's input size). Supports data-URI
        `image_url` parts (PIL-decoded) and raw `image_data` parts
        (base64 float32 + shape)."""
        import base64
        import io

        vision = self.engine.cfg.model.vision
        if vision is None:
            return None
        size = vision.image_size
        out: list[np.ndarray] = []
        for m in messages:
            content = m.get("content")
            if not isinstance(content, list):
                continue
            for part in content:
                if not self._is_image_part(part):
                    continue
                ptype = str(part.get("type", ""))
                if ptype == "image_url":
                    url = (part.get("image_url") or {}).get("url", "")
                    if not url.startswith("data:"):
                        raise ValueError(
                            "only data: URIs are supported for images")
                    from PIL import Image

                    raw = base64.b64decode(url.split(",", 1)[1])
                    img = Image.open(io.BytesIO(raw)).convert("RGB") \
                        .resize((size, size))
                    out.append(np.asarray(img, np.float32) / 255.0)
                elif ptype == "image_data":
                    arr = np.frombuffer(
                        base64.b64decode(part["data"]),
                        np.float32).reshape(part["shape"])
                    out.append(arr.astype(np.float32))
                else:
                    # Must raise: _build_mm_token_ids emits a placeholder
                    # run for EVERY image-typed part, so silently skipping
                    # one here would mis-align the embedding splice.
                    raise ValueError(
                        f"unsupported image part type: {ptype}")
        return np.stack(out) if out else None

    def _encode_pixels(self, pixels: np.ndarray,
                       encode_name: str) -> np.ndarray:
        """ENCODE stage: remote on the routed instance, local fallback.
        Returns flattened [n_images * out_tokens, D] float32."""
        if encode_name and encode_name != self.name:
            r = _requests.post(
                f"http://{encode_name}/rpc/encode",
                data=msgpack.packb({"bytes": pixels.tobytes(),
                                    "shape": list(pixels.shape),
                                    "dtype": "float32"}, use_bin_type=True),
                timeout=60)
            r.raise_for_status()
            obj = msgpack.unpackb(r.content, raw=False)
            embeds = np.frombuffer(obj["bytes"], np.float32) \
                .reshape(obj["shape"])
        else:
            import jax.numpy as jnp

            from ..models.qwen2_vl import encode_images

            embeds = np.asarray(encode_images(
                self.engine.params, self.engine.cfg.model,
                jnp.asarray(pixels)).astype(jnp.float32))
        return embeds.reshape(-1, embeds.shape[-1])

    def _build_mm_token_ids(self, messages: list[dict]) -> list[int]:
        """Token ids for a multimodal prompt: the chat template renders
        normally (each image part becomes one MM_PLACEHOLDER marker), then
        each marker expands to `out_tokens` copies of the model's image
        placeholder token — so multimodal prompts keep the exact same role
        structure/system prompt as text-only ones.

        Note: the service's routing-side token count (one marker per image)
        undercounts the engine's actual prompt by (out_tokens-1) per image;
        usage reported to clients uses the engine's own count."""
        mcfg = self.engine.cfg.model
        out_tokens = mcfg.vision.out_tokens if mcfg.vision else 0
        tok = self.engine.tokenizer
        rendered = self.chat_template.apply(messages)
        ids: list[int] = []
        segments = rendered.split(MM_PLACEHOLDER)
        for i, segment in enumerate(segments):
            if i > 0:
                ids.extend([mcfg.image_token_id] * out_tokens)
            if segment:
                ids.extend(tok.encode(segment))
        return ids

    @staticmethod
    def _sampling_from_body(body: dict[str, Any]) -> SamplingParams:
        sp = SamplingParams()
        def num(key, default, cast):
            v = body.get(key)
            return cast(v) if v is not None else default
        sp.max_tokens = num("max_tokens", num("max_completion_tokens", 16, int), int)
        sp.n = num("n", 1, int)
        sp.temperature = num("temperature", 1.0, float)
        sp.top_p = num("top_p", 1.0, float)
        sp.top_k = num("top_k", -1, int)
        sp.frequency_penalty = num("frequency_penalty", 0.0, float)
        sp.presence_penalty = num("presence_penalty", 0.0, float)
        sp.repetition_penalty = num("repetition_penalty", 1.0, float)
        stop = body.get("stop")
        sp.stop = [stop] if isinstance(stop, str) else \
            [str(s) for s in stop] if isinstance(stop, list) else []
        sp.stop_token_ids = list(body.get("stop_token_ids", ()))
        if body.get("seed") is not None:
            sp.seed = int(body["seed"])
        lp = body.get("logprobs")
        if isinstance(lp, bool):
            sp.logprobs = lp
            sp.top_logprobs = int(body.get("top_logprobs") or 0)
        elif isinstance(lp, int):
            sp.logprobs = lp > 0
            sp.top_logprobs = lp
        sp.ignore_eos = bool(body.get("ignore_eos", False))
        lb = body.get("logit_bias")
        if isinstance(lb, dict):
            try:
                sp.logit_bias = {int(k): float(v) for k, v in lb.items()}
            except (TypeError, ValueError):
                pass
        return sp


def main() -> None:
    from ..models import base as model_base

    p = argparse.ArgumentParser(description="xllm-service-tpu engine agent")
    p.add_argument("--coordination-addr", default="127.0.0.1:12379")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--type", default="MIX",
                   choices=[t.value for t in InstanceType])
    p.add_argument("--model-id", default="bench-1b")
    p.add_argument("--model-config", default="bench_1b",
                   help="config factory in models.base (e.g. bench_1b, "
                        "llama3_8b, llama3_8b_l20, tiny)")
    p.add_argument("--tokenizer-path", default="")
    p.add_argument("--checkpoint-path", default="",
                   help="HF safetensors dir (llama/qwen2 families) or an "
                        "orbax checkpoint dir")
    p.add_argument("--max-batch-size", type=int, default=8)
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--max-seq-len", type=int, default=2048)
    p.add_argument("--dp-size", type=int, default=1,
                   help="model replicas behind this registration")
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel mesh size (0 = single device); "
                        "spans hosts when a multi-host group is joined")
    p.add_argument("--device-offset", type=int, default=0,
                   help="first device index for this instance's mesh: "
                        "co-hosted instances (e.g. a PREFILL/DECODE "
                        "pair on one pod slice) own DISJOINT device "
                        "groups instead of stacking on device 0")
    p.add_argument("--quant", default="", choices=["", "int8"],
                   help="weight-only quantization (models/quant.py)")
    p.add_argument("--decode-horizon", type=int, default=0,
                   help="tokens per decode program call (0 = config default)")
    p.add_argument("--generation-flush-ms", type=float, default=5.0,
                   help="batching window for Generations delta pushes")
    p.add_argument("--speculate-k", type=int, default=0,
                   help="prompt-lookup speculation draft length (0 = off)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="chunked-prefill tokens per engine iteration "
                        "(0 = whole-suffix installs); with a chunk set, "
                        "mid chunks ride decode steps (Sarathi mixed "
                        "programs)")
    p.add_argument("--kv-tier-dram-mb", type=int, default=0,
                   help="host-RAM tier for evicted prefix KV blocks, MiB "
                        "(0 disables tiering; docs/kv_tiering.md)")
    p.add_argument("--kv-tier-ssd-mb", type=int, default=0,
                   help="disk spill tier behind the DRAM arena, MiB "
                        "(0 = DRAM-only; requires --kv-tier-dram-mb > 0 "
                        "— offloads land in DRAM first, SSD is overflow)")
    p.add_argument("--kv-tier-ssd-path", default="",
                   help="spill file path ('' = tempfile owned by the "
                        "store)")
    p.add_argument("--telemetry-mode", default="mux",
                   choices=["mux", "owner", "master"],
                   help="mux = one multiplexed keepalive session to the "
                        "owning master (tagged hb+gens frames); owner = "
                        "heartbeats to the rendezvous owner, deltas "
                        "direct; master = legacy elected-master funnel")
    p.add_argument("--degraded-mode", default="on", choices=["on", "off"],
                   help="on = keep heartbeats flowing to the last-known-"
                        "good master while the coordination plane is "
                        "unreachable (static stability); off = legacy "
                        "behavior (no resolvable target, no beats)")
    p.add_argument("--slice-id", default="slice-0",
                   help="TPU slice/pod this instance's mesh lives on; "
                        "same-slice PD handoffs ride ICI, cross-slice "
                        "rides DCN (docs/topology.md)")
    p.add_argument("--topo-host", default="",
                   help="physical host coordinate; non-empty marks this "
                        "instance PLACED so routing/planner/autoscaler "
                        "cost its links by class ('' = legacy per-host "
                        "synthetic slice, flat behavior)")
    p.add_argument("--topo-chip", type=int, default=-1,
                   help="chip index within --topo-host (-1 = unpinned)")
    p.add_argument("--ici-bytes-per-s", type=float, default=0.0,
                   help="ICI-class KV pull bandwidth budget, bytes/s "
                        "(0 = account-only, no pacing)")
    p.add_argument("--dcn-bytes-per-s", type=float, default=0.0,
                   help="DCN-class KV pull bandwidth budget, bytes/s "
                        "(0 = account-only, no pacing)")
    args = p.parse_args()

    # Multi-host: join the process group (XLLM_MH_COORDINATOR /
    # XLLM_MH_NUM_HOSTS / XLLM_MH_HOST_ID) BEFORE touching devices so
    # jax.devices() — and every mesh built below — is global.
    from ..parallel import multihost

    multihost.initialize_from_env()
    # The devices this process holds, said before anything is built on
    # them: a launcher that must stay off JAX reads this line.
    devs = jax.devices()
    logger.info("jax devices: platform=%s kind=%s count=%d ids=%s",
                devs[0].platform, devs[0].device_kind, len(devs),
                [d.id for d in devs])

    def _gemma_2b():
        from ..models.gemma import gemma_2b_config

        return gemma_2b_config()

    def _gemma_tiny():
        from ..models.gemma import gemma_tiny_config

        return gemma_tiny_config()

    def _mixtral_tiny():
        from ..models.mixtral import mixtral_tiny_config

        return mixtral_tiny_config()

    def _mixtral_8x7b():
        from ..models.mixtral import mixtral_8x7b_config

        return mixtral_8x7b_config()

    def _tiny_f32():
        import jax.numpy as jnp

        # CPU-bench shape: float32 (CPU bf16 emulation is not what any
        # serving comparison wants) and the context the inproc serve
        # bench uses, so multiproc vs inproc measure the SAME model.
        return model_base.tiny_config(dtype=jnp.float32,
                                      max_context_len=1024)

    factory = {
        "tiny": model_base.tiny_config,
        "tiny_f32": _tiny_f32,
        "bench_1b": model_base.bench_1b_config,
        "llama3_8b": model_base.llama3_8b_config,
        "llama3_8b_l20": model_base.llama3_8b_l20_config,
        "llama3_70b": model_base.llama3_70b_config,
        "gemma_2b": _gemma_2b,
        "gemma_tiny": _gemma_tiny,
        "mixtral_8x7b": _mixtral_8x7b,
        "mixtral_tiny": _mixtral_tiny,
    }[args.model_config]
    mcfg = factory()
    if args.quant:
        import dataclasses

        mcfg = dataclasses.replace(mcfg, quant=args.quant)
    max_seq_len = min(args.max_seq_len, mcfg.max_context_len)
    ecfg = EngineConfig(
        model_id=args.model_id, model=mcfg,
        model_family=mcfg.name,
        num_pages=args.num_pages, page_size=args.page_size,
        max_batch_size=args.max_batch_size,
        max_seq_len=max_seq_len,
        prefill_buckets=prefill_bucket_ladder(max_seq_len),
        role=InstanceType.parse(args.type),
        # Pre-compile horizon variants on real chips so the first
        # short-budget request doesn't hit a mid-serving XLA compile.
        warmup_programs=jax.default_backend() != "cpu")
    if args.kv_tier_dram_mb > 0:
        ecfg.kv_tier_dram_bytes = args.kv_tier_dram_mb << 20
        ecfg.kv_tier_ssd_bytes = args.kv_tier_ssd_mb << 20
        ecfg.kv_tier_ssd_path = args.kv_tier_ssd_path
    if args.decode_horizon > 0:
        ecfg.decode_horizon = args.decode_horizon
    if args.prefill_chunk > 0:
        ecfg.prefill_chunk_tokens = args.prefill_chunk
    if args.speculate_k > 0:
        ecfg.speculate_k = args.speculate_k
    if args.tp and args.tp > 1:
        from ..parallel.mesh import MeshConfig

        ecfg.mesh = MeshConfig(model=args.tp)
    if args.device_offset:
        if not (args.tp and args.tp > 1):
            p.error("--device-offset requires --tp > 1 (a mesh to place)")
        if args.device_offset < 0:
            p.error("--device-offset must be >= 0")
        ecfg.mesh_device_offset = args.device_offset
    params = None
    if args.checkpoint_path:
        from pathlib import Path

        from .. import models as _models
        from ..models import loader as _loader
        from ..parallel.mesh import build_mesh as _build_mesh

        # Slice to exactly the devices the mesh asks for, starting at
        # the instance's device offset (matches InferenceEngine's own
        # construction — weights must shard onto the SAME device group
        # the engine runs on, or a co-hosted pair's params collide on
        # device 0's HBM).
        off = ecfg.mesh_device_offset
        mesh = _build_mesh(
            ecfg.mesh,
            devices=jax.devices()[off:off + ecfg.mesh.num_devices()]) \
            if ecfg.mesh else None
        fam = _models.get_model_family(ecfg.model_family)
        if list(Path(args.checkpoint_path).glob("*.safetensors")):
            params = _loader.load_hf_llama_safetensors(
                args.checkpoint_path, mcfg, mesh=mesh,
                rules=fam.sharding_rules)
        else:
            params = _loader.load_params(args.checkpoint_path, mcfg,
                                         mesh=mesh, rules=fam.sharding_rules)
    # Follower hosts never expose HTTP/registration; they mirror the
    # primary's engine events in the lockstep loop until a shutdown
    # event arrives. Validate unsupported combos BEFORE the split so a
    # primary-side config error can't strand followers in a collective.
    if jax.process_count() > 1 and args.dp_size != 1:
        p.error("multihost mode requires --dp-size 1")
    if not multihost.is_primary():
        from .multihost_driver import MultihostEngineDriver

        # The engine must match the primary's EXACTLY — including the
        # tokenizer (eos/stop-token ids feed the jitted decode state;
        # a mismatch desynchronizes the lockstep batch composition).
        tokenizer = TokenizerFactory.create_tokenizer(args.tokenizer_path)
        engine = InferenceEngine(ecfg, tokenizer=tokenizer, params=params)
        MultihostEngineDriver(engine).follower_loop()
        return

    agent = EngineAgent(
        ecfg, AgentConfig(host=args.host, port=args.port,
                          coordination_addr=args.coordination_addr,
                          instance_type=InstanceType.parse(args.type),
                          model_id=args.model_id,
                          tokenizer_path=args.tokenizer_path,
                          generation_flush_ms=args.generation_flush_ms,
                          dp_size=args.dp_size,
                          telemetry_mode=args.telemetry_mode,
                          degraded_mode=args.degraded_mode,
                          slice_id=args.slice_id,
                          topo_host=args.topo_host,
                          topo_chip=args.topo_chip,
                          ici_bytes_per_s=args.ici_bytes_per_s,
                          dcn_bytes_per_s=args.dcn_bytes_per_s),
        params=params)
    agent.start()
    import signal as _signal

    def _sigterm(_sig, _frm):
        # Planned restarts drain: stop taking traffic, finish streams.
        agent.drain(timeout_s=60.0)
        raise SystemExit(0)

    _signal.signal(_signal.SIGTERM, _sigterm)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        agent.stop()


if __name__ == "__main__":
    main()
