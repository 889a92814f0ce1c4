"""Routing-snapshot (RCU) consistency drills.

PR 4 made the scheduling hot path lock-free: `select_instances_pair`,
`bind_request_instance_incarnations`, `has_available_instances` and
`get_channel` read an immutable snapshot published by membership writers.
These drills race heartbeats, evictions, replacements and PD-role flips
against concurrent scheduling and pin the consistency contract:

- a schedule that returns OK is bound to a (name, incarnation) pair that
  was live at some instant during the call — NEVER to an instance evicted
  (or an incarnation replaced) before the call began;
- a drained/SUSPECT/evicted instance disappears from routing as soon as
  its state change publishes;
- readiness and wire negotiation follow the snapshot.

The chaos-marked drill runs the same race through the full HTTP stack
with live streams and the fault plane (and doubles as a race detector
under XLLM_LOCK_DEBUG=1 via the conftest instrumented-lock guard).
"""

import json
import threading
import time
import uuid

import pytest
import requests

from xllm_service_tpu.common.config import ServiceOptions
from xllm_service_tpu.common.faults import FAULTS
from xllm_service_tpu.common.request import Request
from xllm_service_tpu.common.types import InstanceRuntimeState, InstanceType
from xllm_service_tpu.coordination.memory import InMemoryCoordination
from xllm_service_tpu.master import Master
from xllm_service_tpu.rpc.wire import WIRE_JSON, WIRE_MSGPACK
from xllm_service_tpu.scheduler.instance_mgr import InstanceMgr
from xllm_service_tpu.scheduler.scheduler import Scheduler
from xllm_service_tpu.testing.fake_engine import FakeEngine, FakeEngineConfig

from fakes import FakeChannel, make_meta, wait_until


def _mgr(store, **opt_kw) -> InstanceMgr:
    opts = ServiceOptions(reconcile_interval_s=3600,
                          sync_interval_s=3600, **opt_kw)
    return InstanceMgr(InMemoryCoordination(store), opts,
                       channel_factory=FakeChannel.factory,
                       start_threads=False)


class TestSnapshotSemantics:
    def test_suspect_and_draining_leave_routing(self, store):
        FakeChannel.reset()
        mgr = _mgr(store)
        mgr.register_instance(make_meta("a", InstanceType.MIX),
                              link_peers=False)
        mgr.register_instance(make_meta("b", InstanceType.MIX),
                              link_peers=False)
        assert mgr.has_available_instances()
        picked = {mgr.get_next_instance_pair().prefill_name
                  for _ in range(8)}
        assert picked == {"a", "b"}

        with mgr._cluster_lock:
            mgr._set_state(mgr._instances["a"],
                           InstanceRuntimeState.SUSPECT)
        picked = {mgr.get_next_instance_pair().prefill_name
                  for _ in range(8)}
        assert picked == {"b"}

        # Draining flag arrives via a meta refresh: also leaves routing.
        meta_b = mgr.get_instance_meta("b")
        meta_b.draining = True
        mgr._handle_instance_put(meta_b)
        assert not mgr.has_available_instances()
        assert not mgr.get_next_instance_pair().valid()

    def test_bind_fails_for_instance_evicted_after_select(self, store):
        FakeChannel.reset()
        mgr = _mgr(store)
        mgr.register_instance(make_meta("a", InstanceType.MIX),
                              link_peers=False)
        routing = mgr.get_next_instance_pair()
        assert routing.prefill_name == "a"
        mgr.deregister_instance("a", reason="drill")
        req = Request(service_request_id="s", request_id="r", model="m")
        req.routing = routing
        # RCU validation: the CURRENT snapshot no longer holds "a".
        assert not mgr.bind_request_instance_incarnations(req)

    def test_wire_negotiation_and_demotion(self, store):
        FakeChannel.reset()
        mgr = _mgr(store)
        mgr.register_instance(
            make_meta("m", InstanceType.MIX,
                      wire_formats=[WIRE_MSGPACK, WIRE_JSON]),
            link_peers=False)
        mgr.register_instance(make_meta("legacy", InstanceType.MIX),
                              link_peers=False)
        assert mgr.dispatch_wire("m") == WIRE_MSGPACK
        assert mgr.dispatch_wire("legacy") == WIRE_JSON   # default meta
        assert mgr.get_channel("m").wire_format == WIRE_MSGPACK
        mgr.demote_wire("m")
        assert mgr.dispatch_wire("m") == WIRE_JSON
        mgr.demote_wire("m")   # idempotent
        assert mgr.dispatch_wire("m") == WIRE_JSON

    def test_channel_read_is_snapshot_backed(self, store):
        FakeChannel.reset()
        mgr = _mgr(store)
        mgr.register_instance(make_meta("a", InstanceType.MIX),
                              link_peers=False)
        assert mgr.get_channel("a") is FakeChannel.registry["a"]
        mgr.deregister_instance("a", reason="drill")
        assert mgr.get_channel("a") is None


class TestSchedulingRaces:
    """Writers churn the fleet while readers schedule: no OK schedule may
    bind to a pair that was already dead before the call began."""

    def _scheduler(self, store) -> Scheduler:
        sched = Scheduler(ServiceOptions(reconcile_interval_s=3600,
                                         sync_interval_s=3600,
                                         lease_ttl_s=3600),
                          coord=InMemoryCoordination(store),
                          start_threads=False)
        sched.instance_mgr._channel_factory = FakeChannel.factory
        return sched

    def test_evictions_and_replacements_race_schedule(self, store):
        FakeChannel.reset()
        sched = self._scheduler(store)
        mgr = sched.instance_mgr
        names = [f"i{k}" for k in range(4)]
        for n in names:
            mgr.register_instance(make_meta(n, InstanceType.MIX),
                                  link_peers=False)

        dead_lock = threading.Lock()
        dead: set = set()          # (name, incarnation) no longer live
        stop = threading.Event()
        errors: list = []

        def churner(my_names):
            while not stop.is_set():
                for n in my_names:
                    meta = mgr.get_instance_meta(n)
                    if meta is None:
                        continue
                    # Replacement: same name, new incarnation (the
                    # deregister+register path the watch plane takes).
                    # "Dead" means deregistered: until that returns a
                    # reader is legally routed to this incarnation.
                    mgr.deregister_instance(n, reason="replaced")
                    with dead_lock:
                        dead.add((n, meta.incarnation_id))
                    mgr.register_instance(
                        make_meta(n, InstanceType.MIX,
                                  incarnation_id=uuid.uuid4().hex[:8]),
                        link_peers=False)

        def reader():
            while not stop.is_set():
                with dead_lock:
                    dead_before = set(dead)
                req = Request(service_request_id=uuid.uuid4().hex[:8],
                              request_id="r", model="m", prompt="hi")
                status = sched.schedule(req)
                if not status.ok():
                    continue   # churn window: UNAVAILABLE is legal
                pair = (req.routing.prefill_name, req.prefill_incarnation)
                if not req.prefill_incarnation:
                    errors.append(f"unbound OK schedule: {pair}")
                elif pair in dead_before:
                    errors.append(f"routed to stale incarnation: {pair}")

        threads = [threading.Thread(target=churner, args=(names[:2],)),
                   threading.Thread(target=churner, args=(names[2:],))] + \
                  [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        try:
            assert not errors, errors[:5]
        finally:
            sched.stop()

    def test_role_flips_race_schedule(self, store):
        FakeChannel.reset()
        sched = self._scheduler(store)
        mgr = sched.instance_mgr
        for k in range(2):
            mgr.register_instance(make_meta(f"p{k}", InstanceType.PREFILL),
                                  link_peers=False)
            mgr.register_instance(make_meta(f"d{k}", InstanceType.DECODE),
                                  link_peers=False)
        stop = threading.Event()
        errors: list = []

        def flipper():
            flip = True
            while not stop.is_set():
                # p1/d1 swap roles continuously; p0/d0 anchor the fleet.
                mgr.flip_instance_role(
                    "p1", InstanceType.DECODE if flip
                    else InstanceType.PREFILL)
                mgr.flip_instance_role(
                    "d1", InstanceType.PREFILL if flip
                    else InstanceType.DECODE)
                flip = not flip

        def reader():
            while not stop.is_set():
                req = Request(service_request_id=uuid.uuid4().hex[:8],
                              request_id="r", model="m", prompt="hi")
                status = sched.schedule(req)
                if not status.ok():
                    errors.append(status.message)   # anchors always exist
                elif not req.prefill_incarnation:
                    errors.append("unbound OK schedule")

        threads = [threading.Thread(target=flipper)] + \
                  [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(1.2)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        try:
            assert not errors, errors[:5]
        finally:
            sched.stop()


@pytest.mark.chaos
class TestSnapshotChaosDrill:
    """Full-stack: fleet churn (pause/resume + role flips) under live
    streams with the fault plane armed. Every stream must complete with
    the full reply (transparent failover covers any mid-churn binding)."""

    REPLY = "Snapshots never route to the dead."

    def test_streams_survive_fleet_churn(self, store):
        FAULTS.configure((), seed=7)
        opts = ServiceOptions(
            host="127.0.0.1", http_port=0, rpc_port=0,
            lease_ttl_s=0.5, reconcile_interval_s=0.05,
            heartbeat_silence_to_suspect_s=0.3,
            detect_disconnected_instance_interval_s=0.5,
            health_probe_attempts=1, health_probe_timeout_s=0.2,
            sync_interval_s=0.2, failover_backoff_base_s=0.05,
            failover_backoff_max_s=0.3)
        master = Master(opts, coord=InMemoryCoordination(store))
        master.start()
        engines = [
            FakeEngine(InMemoryCoordination(store), FakeEngineConfig(
                reply_text=self.REPLY, chunk_size=4, delay_s=0.03,
                heartbeat_interval_s=0.1, lease_ttl_s=0.5)).start()
            for _ in range(3)]
        base = f"http://127.0.0.1:{master.http_port}"
        try:
            assert wait_until(
                lambda: all(master.scheduler.instance_mgr
                            .get_instance_meta(e.name) is not None
                            for e in engines), timeout=5)
            stop = threading.Event()

            def churner():
                flip = True
                while not stop.is_set():
                    # Role flips + a heartbeat pause/resume cycle on one
                    # engine: SUSPECT → recovery churns the snapshot.
                    master.scheduler.instance_mgr.flip_instance_role(
                        engines[0].name,
                        InstanceType.PREFILL if flip else InstanceType.MIX)
                    engines[1].pause()
                    time.sleep(0.15)
                    engines[1].resume()
                    flip = not flip
                    time.sleep(0.1)

            results, errors = [], []

            def run_stream():
                try:
                    r = requests.post(base + "/v1/completions", json={
                        "model": "fake-model", "prompt": "chaos",
                        "stream": True, "max_tokens": 1000},
                        stream=True, timeout=60)
                    assert r.status_code == 200, r.text
                    text = ""
                    for line in r.iter_lines():
                        if not line.startswith(b"data: ") \
                                or line == b"data: [DONE]":
                            continue
                        obj = json.loads(line[len(b"data: "):])
                        if "error" in obj:
                            raise RuntimeError(str(obj["error"]))
                        for c in obj.get("choices", ()):
                            text += c.get("text", "")
                    results.append(text)
                except Exception as e:  # noqa: BLE001 — collected
                    errors.append(e)

            churn = threading.Thread(target=churner)
            churn.start()
            streams = [threading.Thread(target=run_stream)
                       for _ in range(6)]
            for t in streams:
                t.start()
                time.sleep(0.05)
            for t in streams:
                t.join(timeout=60)
            stop.set()
            churn.join(timeout=10)
            assert not errors, errors
            assert len(results) == 6
            assert all(t == self.REPLY for t in results), results
        finally:
            FAULTS.clear()
            for e in engines:
                e.stop()
            master.stop()
