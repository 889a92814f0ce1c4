"""Scheduler tests: schedule→generation flow, streaming/non-stream delivery,
disconnect cancellation, failure cancel-and-surface, master election."""

import pytest

from xllm_service_tpu.common.call_data import CollectingConnection
from xllm_service_tpu.common.config import ServiceOptions
from xllm_service_tpu.common.request import (
    Request,
    RequestOutput,
    SequenceOutput,
    Status,
    StatusCode,
    Usage,
)
from xllm_service_tpu.common.types import InstanceType
from xllm_service_tpu.coordination.memory import InMemoryCoordination
from xllm_service_tpu.scheduler.scheduler import Scheduler

from fakes import FakeChannel, make_meta, wait_until


@pytest.fixture(autouse=True)
def _reset_channels():
    FakeChannel.reset()
    yield
    FakeChannel.reset()


def make_scheduler(store, **kw):
    coord = InMemoryCoordination(store)
    opts = ServiceOptions(reconcile_interval_s=0.05, sync_interval_s=0.1,
                          lease_ttl_s=0.2, **kw)
    sched = Scheduler(opts, coord=coord, start_threads=False)
    # Swap in fake channels.
    sched.instance_mgr._channel_factory = FakeChannel.factory
    return sched


def fleet(sched, *metas):
    for m in metas:
        sched.instance_mgr.register_instance(m, link_peers=False)


def _drain(sched):
    sched._output_executor.drain()


class TestScheduleFlow:
    def test_schedule_tokenizes_and_routes(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="s1", prompt="hello world")
        st = sched.schedule(req)
        assert st.ok()
        assert req.token_ids
        assert req.routing.prefill_name == "m1"
        assert req.prefill_incarnation
        sched.stop()

    def test_schedule_applies_chat_template(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="s1",
                      messages=[{"role": "user", "content": "hi"}])
        assert sched.schedule(req).ok()
        assert "<|im_start|>user" in req.prompt
        sched.stop()

    def test_schedule_no_instances(self, store):
        sched = make_scheduler(store)
        st = sched.schedule(Request(service_request_id="s1", prompt="x"))
        assert st.code == StatusCode.UNAVAILABLE
        sched.stop()

    def test_streaming_generation_delivery(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="s1", request_id="chatcmpl-1",
                      model="m", stream=True, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection(stream=True)
        sched.record_new_request(req, conn, "chat")
        assert sched.handle_generation(RequestOutput(
            service_request_id="s1",
            outputs=[SequenceOutput(index=0, text="he", token_ids=[1])]))
        assert sched.handle_generation(RequestOutput(
            service_request_id="s1",
            outputs=[SequenceOutput(index=0, text="llo", token_ids=[2],
                                    finish_reason="stop")],
            usage=Usage(1, 2), finished=True))
        _drain(sched)
        assert conn.finished
        content = "".join(
            c["choices"][0]["delta"].get("content") or ""
            for c in conn.payloads if c.get("choices"))
        assert content == "hello"
        assert not sched.has_request("s1")
        # Unknown request now -> engine told to stop.
        assert not sched.handle_generation(RequestOutput(
            service_request_id="s1",
            outputs=[SequenceOutput(index=0, text="x")]))
        sched.stop()

    def test_non_stream_aggregation(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="s2", request_id="cmpl-1",
                      model="m", stream=False, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection()
        sched.record_new_request(req, conn, "completion")
        for i, (txt, fin) in enumerate([("a", ""), ("b", ""), ("c", "stop")]):
            sched.handle_generation(RequestOutput(
                service_request_id="s2",
                outputs=[SequenceOutput(index=0, text=txt, token_ids=[i],
                                        finish_reason=fin)],
                finished=bool(fin)))
        _drain(sched)
        assert conn.finished
        assert conn.payloads[0]["choices"][0]["text"] == "abc"
        assert conn.payloads[0]["usage"]["completion_tokens"] == 3
        sched.stop()

    def test_disconnect_cancels_on_engine(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="s3", request_id="r", model="m",
                      stream=True, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection(stream=True)
        sched.record_new_request(req, conn, "chat")
        conn.disconnected = True
        assert not sched.handle_generation(RequestOutput(
            service_request_id="s3",
            outputs=[SequenceOutput(index=0, text="x", token_ids=[1])]))
        assert "s3" in FakeChannel.registry["m1"].cancels
        assert not sched.has_request("s3")
        sched.stop()

    def test_duplicate_delta_seq_dropped(self, store):
        """A retried Generations POST (same delta_seq) must be acked but
        not re-delivered or re-counted."""
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="d1", request_id="r", model="m",
                      stream=True, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection(stream=True)
        sched.record_new_request(req, conn, "chat")
        out = RequestOutput(
            service_request_id="d1", request_id="r", delta_seq=1,
            outputs=[SequenceOutput(index=0, text="x", token_ids=[1])])
        assert sched.handle_generation(out)
        assert sched.handle_generation(out)   # duplicate: acked, dropped
        _drain(sched)
        assert req.num_generated_tokens == 1
        texts = [p for p in conn.payloads
                 if p["choices"][0]["delta"].get("content") == "x"]
        assert len(texts) == 1
        sched.stop()

    def test_pre_token_exit_paths_leak_no_load(self, store):
        """Disconnect, error, and GC-timeout before the first token must
        leave all load accounting at zero (FINISH_PREFILL on
        those paths leaked decode load; GC leaked prefill load)."""
        sched = make_scheduler(store, request_timeout_s=0.0)
        fleet(sched, make_meta("m1", InstanceType.MIX))

        def loads():
            rl = sched.instance_mgr._request_loads.get("m1")
            if rl is None:
                return (0, 0, 0, 0)
            return (rl.num_prefill_requests, rl.num_prefill_tokens,
                    rl.num_decode_requests, rl.num_decode_tokens)

        # Disconnect path.
        req = Request(service_request_id="g1", request_id="r", model="m",
                      stream=True, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection(stream=True)
        sched.record_new_request(req, conn, "chat")
        conn.disconnected = True
        sched.handle_generation(RequestOutput(
            service_request_id="g1",
            outputs=[SequenceOutput(index=0, text="x", token_ids=[1])]))
        assert loads() == (0, 0, 0, 0)

        # Error-status path.
        req = Request(service_request_id="g2", request_id="r", model="m",
                      stream=False, prompt="hi")
        assert sched.schedule(req).ok()
        sched.record_new_request(req, CollectingConnection(), "chat")
        sched.handle_generation(RequestOutput(
            service_request_id="g2",
            status=Status(StatusCode.RESOURCE_EXHAUSTED, "full"),
            finished=True))
        _drain(sched)
        assert loads() == (0, 0, 0, 0)

        # GC-timeout path (request_timeout_s=0 → instantly stale).
        req = Request(service_request_id="g3", request_id="r", model="m",
                      stream=False, prompt="hi")
        assert sched.schedule(req).ok()
        sched.record_new_request(req, CollectingConnection(), "chat")
        req.latest_generate_time_ms -= 1
        sched._gc_stale_requests()
        _drain(sched)
        assert not sched.has_request("g3")
        assert loads() == (0, 0, 0, 0)
        sched.stop()

    def test_error_status_surfaces(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX))
        req = Request(service_request_id="s4", request_id="r", model="m",
                      stream=False, prompt="hi")
        sched.schedule(req)
        conn = CollectingConnection()
        sched.record_new_request(req, conn, "chat")
        sched.handle_generation(RequestOutput(
            service_request_id="s4",
            status=Status(StatusCode.RESOURCE_EXHAUSTED, "kv pool full"),
            finished=True))
        _drain(sched)
        assert conn.error is not None
        assert "kv pool full" in conn.error[1]
        sched.stop()


class TestFailurePath:
    def test_clear_requests_on_failed_instance(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("p1", InstanceType.PREFILL, incarnation_id="I1"),
              make_meta("d1", InstanceType.DECODE, incarnation_id="I2"))
        req = Request(service_request_id="s5", request_id="r", model="m",
                      stream=True, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection(stream=True)
        sched.record_new_request(req, conn, "chat")
        sched.clear_requests_on_failed_instance(
            req.routing.decode_name, "I2", InstanceType.DECODE)
        _drain(sched)
        assert conn.error is not None and conn.error[0] == 503
        assert not sched.has_request("s5")
        sched.stop()

    def test_failure_of_unrelated_incarnation_spares_request(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX, incarnation_id="I1"))
        req = Request(service_request_id="s6", request_id="r", model="m",
                      stream=True, prompt="hi")
        assert sched.schedule(req).ok()
        conn = CollectingConnection(stream=True)
        sched.record_new_request(req, conn, "chat")
        sched.clear_requests_on_failed_instance("m1", "OTHER", InstanceType.MIX)
        _drain(sched)
        assert conn.error is None
        assert sched.has_request("s6")
        sched.stop()

    def test_heartbeat_feeds_kvcache_mgr(self, store):
        sched = make_scheduler(store)
        fleet(sched, make_meta("m1", InstanceType.MIX, incarnation_id="I1"))
        from xllm_service_tpu.common.hashing import prefix_block_hash_hexes

        toks = list(range(128))
        hashes = prefix_block_hash_hexes(toks, 128)
        assert sched.handle_instance_heartbeat({
            "name": "m1", "incarnation_id": "I1",
            "load_metrics": {"waiting_requests_num": 2},
            "kv_cache_event": {"stored": hashes, "removed": [], "offloaded": []},
        })
        assert sched.kvcache_mgr.match(toks).scores.get("m1") == 1.0
        # Unknown instance heartbeat rejected.
        assert not sched.handle_instance_heartbeat({"name": "ghost",
                                                    "incarnation_id": "x"})
        sched.stop()


class TestMasterElection:
    def test_first_is_master_second_replica_takeover(self, store):
        s1 = make_scheduler(store, rpc_port=9001)
        assert s1.is_master
        s2 = make_scheduler(store, rpc_port=9002)
        assert not s2.is_master
        s1.stop()   # releases master lease -> s2 takes over via watch
        assert wait_until(lambda: s2.is_master, timeout=3.0)
        s2.stop()
