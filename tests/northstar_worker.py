"""North-star-topology worker: runs in its OWN
process on a 64-virtual-device CPU platform (the suite's conftest pins
8) and proves the v5e-64 serving topology's mesh math end to end:

  1. dryrun_multichip(64, northstar=True): train {data 8 x model 8},
     TP-8 decode, EP-8 MoE (16 experts, 2/shard), ring attention seq=8,
     CP paged decode seq=8, pipeline pipe=8.
  2. page-shard divisibility guard: a CP engine whose num_pages doesn't
     divide the seq axis must refuse at construction, not corrupt pages.
  3. a REAL InferenceEngine decoding context-parallel at seq=8.
  4. PD across host groups: master + prefill agent on devices [0:8] +
     decode agent on devices [32:40] (disjoint groups via
     mesh_device_offset), one greedy completion through the full HTTP
     path with device KV handoff between the groups.

Prints one "OK <section>" line per proof; tests/test_northstar_topology
asserts all of them. (BASELINE.json "v5e-64"; SURVEY §2.12/§2.13.)
"""

import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import __graft_entry__ as graft  # noqa: E402

N = 64


def main() -> None:
    graft._pin_cpu_platform(N)

    # ---- 1. full dryrun battery at north-star axis sizes ----
    graft.dryrun_multichip(N, northstar=True)
    print("OK northstar_dryrun")

    import jax
    import jax.numpy as jnp
    import requests

    from xllm_service_tpu.common.config import ServiceOptions
    from xllm_service_tpu.common.request import SamplingParams
    from xllm_service_tpu.common.types import InstanceType
    from xllm_service_tpu.coordination.memory import (InMemoryCoordination,
                                                      MemoryStore)
    from xllm_service_tpu.engine.agent import AgentConfig, EngineAgent
    from xllm_service_tpu.engine.config import EngineConfig
    from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
    from xllm_service_tpu.master import Master
    from xllm_service_tpu.models.base import tiny_config
    from xllm_service_tpu.parallel.mesh import MeshConfig

    assert len(jax.devices()) >= N

    def cp_cfg(num_pages: int) -> EngineConfig:
        return EngineConfig(
            model_id="ns-cp",
            model=tiny_config(dtype=jnp.float32, num_heads=8,
                              num_kv_heads=8, max_context_len=256),
            mesh=MeshConfig(seq=8),
            num_pages=num_pages, page_size=16, hash_block_size=32,
            max_batch_size=2, max_seq_len=256,
            prefill_buckets=(32, 256), seq_parallel_min_tokens=64)

    # ---- 2. page-shard divisibility must be refused at seq=8 ----
    try:
        InferenceEngine(cp_cfg(num_pages=100))   # 100 % 8 != 0
        raise SystemExit("divisibility guard MISSING: engine accepted a "
                         "page pool that does not shard over seq=8")
    except ValueError as e:
        assert "num_pages" in str(e), e
    print("OK page_shard_divisibility_guard")

    # ---- 3. real CP engine decoding at seq=8 ----
    eng = InferenceEngine(cp_cfg(num_pages=96))
    got: list[int] = []
    eng.submit(EngineRequest(
        "ns-cp-req", token_ids=list(range(2, 82)),
        sampling=SamplingParams(max_tokens=8, temperature=0.0,
                                ignore_eos=True),
        on_output=lambda out: got.extend(
            t for s in out.outputs for t in s.token_ids)))
    for _ in range(40):
        eng.step()
        if len(got) >= 8:
            break
    assert len(got) >= 8, f"CP engine produced {len(got)} tokens"
    eng.stop()
    print("OK cp8_engine_decode")

    # ---- 4. PD pair on DISJOINT device groups + device KV handoff ----
    def pd_cfg() -> EngineConfig:
        return EngineConfig(
            model_id="ns-pd",
            model=tiny_config(dtype=jnp.float32, num_heads=8,
                              num_kv_heads=8, max_context_len=256),
            num_pages=64, page_size=16, hash_block_size=32,
            max_batch_size=2, max_seq_len=256, prefill_buckets=(32, 256))

    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=2.0, sync_interval_s=0.3,
                          reconcile_interval_s=0.1)
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()

    def agent(itype: InstanceType, offset: int) -> EngineAgent:
        cfg = pd_cfg()
        cfg.mesh = MeshConfig(model=8)
        cfg.mesh_device_offset = offset
        return EngineAgent(
            cfg,
            AgentConfig(host="127.0.0.1", model_id="ns-pd",
                        instance_type=itype,
                        heartbeat_interval_s=0.3, lease_ttl_s=2.0,
                        enable_device_kv_transfer=True),
            coord=InMemoryCoordination(store)).start()

    prefill = agent(InstanceType.PREFILL, 0)      # host group 0
    decode = agent(InstanceType.DECODE, 32)       # host group 4
    try:
        import time
        deadline = time.time() + 60
        mgr = master.scheduler.instance_mgr
        while time.time() < deadline:
            if (mgr.get_instance_meta(prefill.name) is not None
                    and mgr.get_instance_meta(decode.name) is not None):
                break
            time.sleep(0.1)
        else:
            raise SystemExit("PD agents never registered")

        pre_devs = {d.id for d in prefill.engine.mesh.devices.flat}
        dec_devs = {d.id for d in decode.engine.mesh.devices.flat}
        assert pre_devs == set(range(8)), pre_devs
        assert dec_devs == set(range(32, 40)), dec_devs
        assert not (pre_devs & dec_devs), "device groups overlap"

        r = requests.post(
            f"http://127.0.0.1:{master.http_port}/v1/completions",
            json={"model": "ns-pd", "prompt": "cross slice handoff",
                  "max_tokens": 8, "temperature": 0, "ignore_eos": True},
            timeout=300)
        assert r.status_code == 200, r.text[:300]
        assert r.json()["choices"][0]["finish_reason"] == "length"
    finally:
        prefill.stop()
        decode.stop()
        master.stop()
        store.close()
    print("OK pd_disjoint_device_groups")


if __name__ == "__main__":
    main()
