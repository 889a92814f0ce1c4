"""Checkpoint B (SURVEY.md §7.2): client → master → REAL JAX engine →
streamed tokens. Runs the tiny model on CPU; same stack as TPU deployment.
"""

import json

import jax.numpy as jnp
import pytest
import requests

from xllm_service_tpu.common.config import ServiceOptions
from xllm_service_tpu.common.types import InstanceType
from xllm_service_tpu.coordination.memory import InMemoryCoordination
from xllm_service_tpu.engine.agent import AgentConfig, EngineAgent
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.master import Master
from xllm_service_tpu.models.base import tiny_config

from fakes import wait_until


@pytest.fixture(scope="module")
def cluster(request):
    from xllm_service_tpu.coordination.memory import MemoryStore

    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=1.0, sync_interval_s=0.3,
                          reconcile_interval_s=0.1)
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()
    ecfg = EngineConfig(
        model_id="tiny-llama",
        model=tiny_config(dtype=jnp.float32, max_context_len=256),
        num_pages=64, page_size=16, hash_block_size=32,
        max_batch_size=4, max_seq_len=256, prefill_buckets=(32, 64, 256))
    agent = EngineAgent(
        ecfg,
        AgentConfig(host="127.0.0.1", model_id="tiny-llama",
                    heartbeat_interval_s=0.3, lease_ttl_s=1.0),
        coord=InMemoryCoordination(store))
    agent.start()
    assert wait_until(
        lambda: master.scheduler.instance_mgr.get_instance_meta(agent.name)
        is not None, timeout=10)
    yield master, agent
    agent.stop()
    master.stop()
    store.close()


def _base(master):
    return f"http://127.0.0.1:{master.http_port}"


class TestRealEngineE2E:
    def test_non_stream_completion(self, cluster):
        master, agent = cluster
        r = requests.post(_base(master) + "/v1/completions", json={
            "model": "tiny-llama", "prompt": "Hello world, this is a test",
            "max_tokens": 8, "temperature": 0, "ignore_eos": True,
        }, timeout=120)
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["usage"]["completion_tokens"] == 8
        assert body["choices"][0]["finish_reason"] == "length"

    def test_streaming_chat_and_determinism(self, cluster):
        master, agent = cluster

        def run_once():
            r = requests.post(_base(master) + "/v1/chat/completions", json={
                "model": "tiny-llama",
                "messages": [{"role": "user", "content": "count to five"}],
                "max_tokens": 6, "temperature": 0, "ignore_eos": True,
                "stream": True,
            }, stream=True, timeout=120)
            assert r.status_code == 200
            chunks = []
            for line in r.iter_lines():
                if line.startswith(b"data: ") and line != b"data: [DONE]":
                    chunks.append(json.loads(line[6:]))
            return "".join(c["choices"][0]["delta"].get("content") or ""
                           for c in chunks if c.get("choices"))

        text1, text2 = run_once(), run_once()
        assert text1 == text2   # greedy => deterministic
        assert len(text1) > 0

    def test_logprobs_over_http(self, cluster):
        master, agent = cluster
        r = requests.post(_base(master) + "/v1/chat/completions", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 3, "temperature": 0, "ignore_eos": True,
            "logprobs": True, "top_logprobs": 2,
        }, timeout=120)
        body = r.json()
        lp = body["choices"][0]["logprobs"]["content"]
        assert len(lp) == 3
        assert len(lp[0]["top_logprobs"]) == 2

    def test_heartbeat_populates_kv_index_and_load(self, cluster):
        master, agent = cluster
        # 64+ token prompt → at least one 32-token hash block cached.
        requests.post(_base(master) + "/v1/completions", json={
            "model": "tiny-llama", "prompt": "x" * 200, "max_tokens": 2,
            "temperature": 0, "ignore_eos": True}, timeout=120)
        assert wait_until(
            lambda: master.scheduler.kvcache_mgr.num_blocks() > 0, timeout=10)
        infos = master.scheduler.instance_mgr.get_load_infos()
        assert agent.name in infos

    def test_engine_stats_endpoint(self, cluster):
        master, agent = cluster
        r = requests.get(f"http://{agent.name}/stats", timeout=5)
        stats = r.json()
        assert "kv_usage_perc" in stats and "cached_blocks" in stats


class TestNChoices:
    def test_n_greater_than_one(self, cluster):
        master, agent = cluster
        r = requests.post(_base(master) + "/v1/completions", json={
            "model": "tiny-llama", "prompt": "pick a number",
            "max_tokens": 4, "temperature": 0, "ignore_eos": True, "n": 3,
        }, timeout=120)
        assert r.status_code == 200, r.text
        body = r.json()
        choices = body["choices"]
        assert sorted(c["index"] for c in choices) == [0, 1, 2]
        # Greedy => all three choices identical text.
        assert len({c["text"] for c in choices}) == 1
        assert all(c["finish_reason"] == "length" for c in choices)
        assert body["usage"]["completion_tokens"] == 12
        assert body["usage"]["prompt_tokens"] > 0

    def test_n_with_seed_distinct_choices(self, cluster):
        master, agent = cluster
        r = requests.post(_base(master) + "/v1/completions", json={
            "model": "tiny-llama", "prompt": "vary " * 30,
            "max_tokens": 5, "temperature": 1.5, "top_k": 200, "seed": 7,
            "ignore_eos": True, "n": 2,
        }, timeout=120)
        body = r.json()
        assert len(body["choices"]) == 2
        # Per-choice seeds (seed+k) should usually give distinct samples.
        texts = {c["text"] for c in body["choices"]}
        assert len(texts) == 2


class TestAgentMetrics:
    def test_prometheus_metrics(self, cluster):
        master, agent = cluster
        r = requests.get(f"http://{agent.name}/metrics", timeout=5)
        assert r.status_code == 200
        assert "engine_generated_tokens_total" in r.text
        assert "engine_kv_usage_perc" in r.text
        assert "engine_sarathi_rides_total" in r.text


class TestLiveProfilingTables:
    def test_tables_fit_from_measured_traffic(self, cluster):
        """After real traffic, the agent's advertised SLO tables come from
        engine telemetry (not the cold-start defaults) and the master's
        predictor refits from them on heartbeat re-registration."""
        master, agent = cluster
        # Drive traffic at a few distinct prompt lengths so >= 3 TTFT
        # buckets exist.
        for words in (4, 20, 60):
            r = requests.post(_base(master) + "/v1/completions", json={
                "model": "tiny-llama", "prompt": "tok " * words,
                "max_tokens": 6, "temperature": 0, "ignore_eos": True},
                timeout=120)
            assert r.status_code == 200, r.text
        assert len(agent.engine.telemetry.admissions) >= 3
        ttft_table, tpot_table = agent.profiling_tables()
        assert ttft_table != agent.DEFAULT_TTFT_TABLE
        assert len(ttft_table) >= 3
        assert all(ms > 0 for _, ms in ttft_table)
        # The next heartbeat re-registers with the measured tables; the
        # master's predictor must refit from them.
        assert wait_until(
            lambda: master.scheduler.instance_mgr.get_instance_meta(
                agent.name).ttft_profiling_data == ttft_table
            or agent.profiling_tables()[0] !=
            ttft_table, timeout=10)
        entry = master.scheduler.instance_mgr._instances[agent.name]
        assert entry.predictor.has_ttft
        # Predictor reflects the measured scale (tiny CPU model: TTFT well
        # under the 30ms+ cold-start default at short prompts).
        measured = entry.predictor.predict_ttft(16)
        assert measured >= 0.0


class TestGracefulDrain:
    def test_drain_excludes_from_scheduling_and_finishes_inflight(self,
                                                                  store):
        """A draining instance takes no new traffic (scheduler excludes it
        on the next refresh) but its in-flight stream finishes intact —
        the reference kills instances abruptly (cancel-and-surface)."""
        import threading

        from xllm_service_tpu.coordination.memory import InMemoryCoordination

        opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                              lease_ttl_s=1.0, sync_interval_s=0.3,
                              reconcile_interval_s=0.1)
        master = Master(opts, coord=InMemoryCoordination(store))
        master.start()
        ecfg = EngineConfig(
            model_id="tiny-llama",
            model=tiny_config(dtype=jnp.float32, max_context_len=256),
            num_pages=64, page_size=16, hash_block_size=32,
            max_batch_size=4, max_seq_len=256,
            prefill_buckets=(32, 64, 256))
        agent = EngineAgent(
            ecfg,
            AgentConfig(host="127.0.0.1", model_id="tiny-llama",
                        heartbeat_interval_s=0.2, lease_ttl_s=1.0),
            coord=InMemoryCoordination(store)).start()
        try:
            assert wait_until(
                lambda: master.scheduler.instance_mgr.get_instance_meta(
                    agent.name) is not None, timeout=10)
            base = f"http://127.0.0.1:{master.http_port}"

            # Long-running streaming request in flight during the drain.
            result = {}

            def long_req():
                r = requests.post(base + "/v1/completions", json={
                    "model": "tiny-llama", "prompt": "drain me",
                    "max_tokens": 40, "temperature": 0,
                    "ignore_eos": True, "stream": True},
                    stream=True, timeout=120)
                chunks = [ln for ln in r.iter_lines()
                          if ln.startswith(b"data: ")]
                result["done"] = chunks[-1] == b"data: [DONE]"
                result["n"] = len(chunks)

            t = threading.Thread(target=long_req)
            t.start()
            assert wait_until(
                lambda: agent.aggregate_stats()["running"] > 0, timeout=30)

            dr = threading.Thread(target=agent.drain,
                                  kwargs={"timeout_s": 60})
            dr.start()
            # Scheduler stops routing here once the draining flag lands.
            assert wait_until(
                lambda: not master.scheduler.has_available_instances(),
                timeout=10)
            r = requests.post(base + "/v1/completions", json={
                "model": "tiny-llama", "prompt": "new", "max_tokens": 4},
                timeout=30)
            assert r.status_code == 503
            t.join(timeout=120)
            dr.join(timeout=120)
            assert result.get("done"), result
            assert result["n"] > 2
        finally:
            master.stop()


class TestEmbeddings:
    def test_embeddings_end_to_end(self, cluster):
        """/v1/embeddings through the full stack (the reference 501s this
        endpoint; we serve mean-pooled final hidden states)."""
        master, agent = cluster
        base = _base(master)
        r = requests.post(base + "/v1/embeddings", json={
            "model": "tiny-llama",
            "input": ["hello world", "a completely different sentence"],
        }, timeout=120)
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["object"] == "list"
        assert len(body["data"]) == 2
        v0 = body["data"][0]["embedding"]
        v1 = body["data"][1]["embedding"]
        assert len(v0) == agent.engine.cfg.model.hidden_size
        assert v0 != v1
        assert body["usage"]["prompt_tokens"] > 0
        # Same input -> same vector (up to batch-shape-dependent float
        # reduction order: the two calls run at different padded batch
        # sizes).
        import numpy as _np

        r2 = requests.post(base + "/v1/embeddings", json={
            "model": "tiny-llama", "input": "hello world"}, timeout=120)
        _np.testing.assert_allclose(
            _np.asarray(r2.json()["data"][0]["embedding"]),
            _np.asarray(v0), rtol=1e-4, atol=1e-5)


class TestEcho:
    def test_completions_echo(self, cluster):
        master, agent = cluster
        base = _base(master)
        body = {"model": "tiny-llama", "prompt": "echo this prompt",
                "max_tokens": 4, "temperature": 0, "ignore_eos": True,
                "echo": True}
        r = requests.post(base + "/v1/completions", json=body, timeout=120)
        assert r.status_code == 200, r.text
        assert r.json()["choices"][0]["text"].startswith("echo this prompt")

        r = requests.post(base + "/v1/completions",
                          json={**body, "stream": True}, stream=True,
                          timeout=120)
        chunks = [json.loads(ln[6:]) for ln in r.iter_lines()
                  if ln.startswith(b"data: ") and ln != b"data: [DONE]"]
        texts = [c["choices"][0]["text"] for c in chunks if c["choices"]]
        assert texts[0] == "echo this prompt"


class TestNChoices:
    def test_n_choices_end_to_end(self, cluster):
        """n=2 fans out into two engine sequences on one replica (the
        prefix cache dedupes the shared prompt through burst admission's
        flush) and the response carries both choices, greedy-identical."""
        master, agent = cluster
        base = _base(master)
        r = requests.post(base + "/v1/completions", json={
            "model": "tiny-llama", "prompt": [11, 12, 13, 14, 15] * 8,
            "max_tokens": 6, "temperature": 0, "ignore_eos": True,
            "n": 2}, timeout=120)
        assert r.status_code == 200, r.text
        choices = r.json()["choices"]
        assert len(choices) == 2
        assert {c["index"] for c in choices} == {0, 1}
        # Greedy: both choices decode the same continuation.
        assert choices[0]["text"] == choices[1]["text"]
        assert all(c["finish_reason"] == "length" for c in choices)
        usage = r.json()["usage"]
        assert usage["completion_tokens"] == 12   # 6 per choice

    def test_n_choices_distinct_when_sampled(self, cluster):
        master, agent = cluster
        base = _base(master)
        r = requests.post(base + "/v1/completions", json={
            "model": "tiny-llama", "prompt": [21, 22, 23, 24] * 6,
            "max_tokens": 8, "temperature": 1.3, "seed": 7,
            "ignore_eos": True, "n": 2}, timeout=120)
        assert r.status_code == 200, r.text
        choices = r.json()["choices"]
        assert len(choices) == 2
        # Seeded sampling: per-choice seeds differ (seed, seed+1), so the
        # streams are deterministic but (with high probability at this
        # temperature and vocab) not identical.
        assert choices[0]["text"] != choices[1]["text"]


class TestAnthropicMessages:
    def test_messages_non_stream(self, cluster):
        """Anthropic Messages API over the chat pipeline (the reference
        only acknowledges anthropic.proto as an engine contract; here it
        is a served endpoint)."""
        master, agent = cluster
        base = _base(master)
        r = requests.post(base + "/v1/messages", json={
            "model": "tiny-llama", "max_tokens": 6,
            "system": "You are terse.",
            "messages": [{"role": "user", "content": "hello"}],
            "temperature": 0, "ignore_eos": True,
        }, timeout=120)
        assert r.status_code == 200, r.text
        body = r.json()
        assert body["type"] == "message"
        assert body["role"] == "assistant"
        assert body["id"].startswith("msg_")
        assert body["content"][0]["type"] == "text"
        assert body["content"][0]["text"]
        assert body["stop_reason"] == "max_tokens"
        assert body["usage"]["input_tokens"] > 0
        assert body["usage"]["output_tokens"] == 6

    def test_messages_missing_max_tokens(self, cluster):
        master, _ = cluster
        r = requests.post(_base(master) + "/v1/messages", json={
            "model": "tiny-llama",
            "messages": [{"role": "user", "content": "x"}]}, timeout=30)
        assert r.status_code == 400

    def test_messages_streaming_event_sequence(self, cluster):
        master, _ = cluster
        r = requests.post(_base(master) + "/v1/messages", json={
            "model": "tiny-llama", "max_tokens": 5, "stream": True,
            "messages": [{"role": "user",
                          "content": [{"type": "text", "text": "hi"}]}],
            "temperature": 0, "ignore_eos": True,
        }, stream=True, timeout=120)
        assert r.status_code == 200
        events = []
        for ln in r.iter_lines():
            if ln.startswith(b"event: "):
                events.append(ln[7:].decode())
        assert events[0] == "message_start"
        assert events[1] == "content_block_start"
        assert "content_block_delta" in events
        assert events[-3:] == ["content_block_stop", "message_delta",
                               "message_stop"]
