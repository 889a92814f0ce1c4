"""Tensor-parallel engine serving e2e: an engine whose mesh shards the
model over the `model` axis (GSPMD rules) must serve through the full
stack with output identical to a single-device engine, including PD
disaggregation over the host KV-transfer path."""

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
from xllm_service_tpu.parallel.mesh import MeshConfig

from fakes import wait_until

BODY = {"model": "tiny-llama", "prompt": "shard me across the mesh",
        "max_tokens": 6, "temperature": 0, "ignore_eos": True}


def _cfg(tp=1) -> EngineConfig:
    return EngineConfig(
        model_id="tiny-llama",
        # kv heads divisible by tp for head sharding.
        model=tiny_config(dtype=jnp.float32, max_context_len=256,
                          num_heads=4, num_kv_heads=2),
        mesh=MeshConfig(model=tp) if tp > 1 else None,
        num_pages=64, page_size=16, hash_block_size=32,
        max_batch_size=4, max_seq_len=256, prefill_buckets=(32, 64, 256))


def _cluster(tp, itypes=(InstanceType.MIX,)):
    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=1.0, sync_interval_s=0.3,
                          reconcile_interval_s=0.1)
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()
    agents = []
    for itype in itypes:
        a = EngineAgent(
            _cfg(tp),
            AgentConfig(host="127.0.0.1", model_id="tiny-llama",
                        instance_type=itype,
                        heartbeat_interval_s=0.3, lease_ttl_s=1.0),
            coord=InMemoryCoordination(store)).start()
        agents.append(a)
    assert wait_until(
        lambda: all(master.scheduler.instance_mgr.get_instance_meta(a.name)
                    is not None for a in agents), timeout=10)
    return master, agents, store


def _run(master):
    r = requests.post(f"http://127.0.0.1:{master.http_port}/v1/completions",
                      json=BODY, timeout=180)
    assert r.status_code == 200, r.text
    return r.json()["choices"][0]["text"]


class TestTensorParallelServing:
    def test_tp2_matches_single_device(self):
        m1, a1, s1 = _cluster(tp=1)
        try:
            want = _run(m1)
        finally:
            for a in a1:
                a.stop()
            m1.stop()
            s1.close()

        m2, a2, s2 = _cluster(tp=2)
        try:
            assert a2[0].engine.mesh is not None
            assert a2[0].engine.mesh.shape["model"] == 2
            meta = m2.scheduler.instance_mgr.get_instance_meta(a2[0].name)
            assert meta.topology.num_devices() == 2
            got = _run(m2)
        finally:
            for a in a2:
                a.stop()
            m2.stop()
            s2.close()
        assert got == want

    def test_tp2_pd_disaggregation_device_path(self):
        """PD pair of TP-sharded engines with identical mesh topologies:
        the handoff rides the device path shard-for-shard (the pull
        reconstructs the sender's partition spec on the receiver's mesh)
        and output matches MIX."""
        m1, a1, s1 = _cluster(tp=2)
        try:
            want = _run(m1)
        finally:
            for a in a1:
                a.stop()
            m1.stop()
            s1.close()

        m2, a2, s2 = _cluster(tp=2, itypes=(InstanceType.PREFILL,
                                            InstanceType.DECODE))
        try:
            prefill, decode = a2
            assert prefill.kv_transfer is not None
            got = _run(m2)
            assert prefill.kv_device_sent == 1
            assert decode.kv_device_received == 1
        finally:
            for a in a2:
                a.stop()
            m2.stop()
            s2.close()
        assert got == want
