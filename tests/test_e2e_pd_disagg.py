"""Checkpoint C (SURVEY.md §7.2): PD-disaggregated serving — prefill and
decode on separate engine instances with KV handoff; output must equal the
single-instance (MIX) result."""

import json

import jax.numpy as jnp
import pytest
import requests

from xllm_service_tpu.common.config import ServiceOptions
from xllm_service_tpu.common.types import InstanceType
from xllm_service_tpu.coordination.memory import InMemoryCoordination, MemoryStore
from xllm_service_tpu.engine.agent import AgentConfig, EngineAgent
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.master import Master
from xllm_service_tpu.models.base import tiny_config

from fakes import wait_until


def _engine_cfg() -> EngineConfig:
    return EngineConfig(
        model_id="tiny-llama",
        model=tiny_config(dtype=jnp.float32, max_context_len=256),
        num_pages=64, page_size=16, hash_block_size=32,
        max_batch_size=4, max_seq_len=256, prefill_buckets=(32, 64, 256))


def _agent(store, itype: InstanceType, device_kv: bool = True) -> EngineAgent:
    return EngineAgent(
        _engine_cfg(),
        AgentConfig(host="127.0.0.1", model_id="tiny-llama",
                    instance_type=itype,
                    heartbeat_interval_s=0.3, lease_ttl_s=1.0,
                    enable_device_kv_transfer=device_kv),
        coord=InMemoryCoordination(store)).start()


@pytest.fixture(scope="module")
def pd_cluster():
    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=1.0, sync_interval_s=0.3,
                          reconcile_interval_s=0.1)
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()
    prefill = _agent(store, InstanceType.PREFILL)
    decode = _agent(store, InstanceType.DECODE)
    assert wait_until(
        lambda: master.scheduler.instance_mgr.get_instance_meta(prefill.name)
        is not None
        and master.scheduler.instance_mgr.get_instance_meta(decode.name)
        is not None, timeout=10)
    yield master, prefill, decode
    prefill.stop()
    decode.stop()
    master.stop()
    store.close()


def _base(master):
    return f"http://127.0.0.1:{master.http_port}"


BODY = {
    "model": "tiny-llama", "prompt": "disaggregate me please",
    "max_tokens": 6, "temperature": 0, "ignore_eos": True,
}


class TestPDDisaggregation:
    def test_pair_routing_and_linking(self, pd_cluster):
        master, prefill, decode = pd_cluster
        # The two instances were introduced to each other at registration.
        assert wait_until(lambda: decode.name in prefill.linked_peers
                          or prefill.name in decode.linked_peers, timeout=5)

    def test_pd_completion_matches_mix(self, pd_cluster):
        master, prefill, decode = pd_cluster
        r = requests.post(_base(master) + "/v1/completions", json=BODY,
                          timeout=120)
        assert r.status_code == 200, r.text
        pd_body = r.json()
        assert pd_body["choices"][0]["finish_reason"] == "length"
        assert pd_body["usage"]["completion_tokens"] == 6
        pd_text = pd_body["choices"][0]["text"]

        # Decode emitted the whole stream (prefill-only sequences emit
        # nothing locally); prefill holds no residual running sequences.
        assert decode.engine.stats()["total_generated"] >= 6
        assert prefill.engine.stats()["running"] == 0
        # Prefill cached the prompt's full blocks (hash block = 32 tokens is
        # longer than this prompt — so only the decode prefix-cache check in
        # the dedicated test below applies; here just assert no leak).
        assert prefill.engine.page_mgr.usage_perc() < 0.5

        # Same request on a MIX-only cluster must produce the same text
        # (same seed => same weights; greedy decoding).
        store2 = MemoryStore(expiry_tick_s=0.05)
        opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                              lease_ttl_s=1.0, sync_interval_s=0.3)
        m2 = Master(opts, coord=InMemoryCoordination(store2))
        m2.start()
        mix = _agent(store2, InstanceType.MIX)
        try:
            assert wait_until(
                lambda: m2.scheduler.instance_mgr.get_instance_meta(mix.name)
                is not None, timeout=10)
            r2 = requests.post(f"http://127.0.0.1:{m2.http_port}"
                               "/v1/completions", json=BODY, timeout=120)
            assert r2.status_code == 200, r2.text
            assert r2.json()["choices"][0]["text"] == pd_text
        finally:
            mix.stop()
            m2.stop()
            store2.close()

    def test_pd_streaming(self, pd_cluster):
        master, prefill, decode = pd_cluster
        r = requests.post(_base(master) + "/v1/completions",
                          json={**BODY, "stream": True}, stream=True,
                          timeout=120)
        assert r.status_code == 200
        events = [line for line in r.iter_lines()
                  if line.startswith(b"data: ")]
        assert events[-1] == b"data: [DONE]"
        texts = [json.loads(e[6:])["choices"][0]["text"]
                 for e in events[:-1] if b'"choices"' in e]
        assert len("".join(texts)) > 0

    def test_device_transfer_path_used(self, pd_cluster):
        """With transfer servers available on both sides, the handoff must
        ride the device path (KV pulled device-to-device), not the host
        msgpack bounce."""
        master, prefill, decode = pd_cluster
        assert prefill.kv_transfer is not None
        assert decode.kv_transfer is not None
        before = prefill.kv_device_sent
        r = requests.post(_base(master) + "/v1/completions", json=BODY,
                          timeout=120)
        assert r.status_code == 200, r.text
        assert prefill.kv_device_sent == before + 1
        assert prefill.kv_host_sent == 0
        assert decode.kv_device_received >= 1
        assert decode.kv_host_received == 0

    def test_unlinked_peer_handoff_rejected(self, pd_cluster):
        """The link-time KV-layout gate only protects if the transfer
        itself enforces the link: a handoff from an unlinked sender must
        be refused."""
        import msgpack as _mp

        _, _, decode = pd_cluster
        msg = _mp.packb({
            "service_request_id": "rogue-1", "request_id": "rogue-1",
            "source_service_addr": "127.0.0.1:1", "token_ids": [1, 2, 3],
            "first_token": 1, "sampling": {},
            "source_instance": "127.0.0.1:59999",   # never linked
            "kv": {"bytes": b"", "shape": [0], "dtype": "float32"},
        }, use_bin_type=True)
        r = requests.post(f"http://{decode.name}/rpc/kv_transfer",
                          data=msg,
                          headers={"Content-Type": "application/msgpack"},
                          timeout=30)
        assert r.status_code == 403

    def test_decode_kv_transfer_populates_prefix_cache(self, pd_cluster):
        master, prefill, decode = pd_cluster
        requests.post(_base(master) + "/v1/completions",
                      json={**BODY, "prompt": "cache this prefix " * 8},
                      timeout=120)
        # Both sides should now hold prefix blocks (prompt >= 1 hash block).
        assert wait_until(
            lambda: prefill.engine.stats()["cached_blocks"] > 0, timeout=5)
        assert wait_until(
            lambda: decode.engine.stats()["cached_blocks"] > 0, timeout=5)


class TestHostFallbackPath:
    def test_host_path_matches_device_path(self, pd_cluster):
        """The DCN host-msgpack fallback (device transfer disabled) must
        produce the same output as the device path — same PrefillHandoff
        contract, different transport."""
        master, _, _ = pd_cluster
        device_text = requests.post(
            _base(master) + "/v1/completions", json=BODY,
            timeout=120).json()["choices"][0]["text"]

        store2 = MemoryStore(expiry_tick_s=0.05)
        opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                              lease_ttl_s=1.0, sync_interval_s=0.3,
                              reconcile_interval_s=0.1)
        m2 = Master(opts, coord=InMemoryCoordination(store2))
        m2.start()
        p2 = _agent(store2, InstanceType.PREFILL, device_kv=False)
        d2 = _agent(store2, InstanceType.DECODE, device_kv=False)
        try:
            assert p2.kv_transfer is None and d2.kv_transfer is None
            assert wait_until(
                lambda: m2.scheduler.instance_mgr.get_instance_meta(p2.name)
                is not None
                and m2.scheduler.instance_mgr.get_instance_meta(d2.name)
                is not None, timeout=10)
            r = requests.post(f"http://127.0.0.1:{m2.http_port}"
                              "/v1/completions", json=BODY, timeout=120)
            assert r.status_code == 200, r.text
            assert r.json()["choices"][0]["text"] == device_text
            assert p2.kv_host_sent == 1 and p2.kv_device_sent == 0
            assert d2.kv_host_received == 1
        finally:
            p2.stop()
            d2.stop()
            m2.stop()
            store2.close()
