"""Engine-level Pallas routing, hermetic on CPU.

XLLM_PALLAS_INTERPRET=1 makes the dispatch gates treat the CPU backend as
kernel-capable and run every Pallas kernel in interpret mode, so these
tests drive the REAL trace-time routing (the (pool, layer) decode kernel
behind the in-place append) end-to-end through the engine and compare
greedy outputs against the default XLA paths. Tiny 1-layer config with
head_dim=128 (the Mosaic lane-width requirement the gates check).
"""

import jax.numpy as jnp

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.base import tiny_config

from test_engine import Collector, run_requests


def _pallas_capable_engine(**kw) -> InferenceEngine:
    cfg = EngineConfig(
        model=tiny_config(dtype=jnp.float32, hidden_size=128,
                          num_heads=2, num_kv_heads=1, head_dim=128,
                          num_layers=1, ffn_size=128,
                          max_context_len=128),
        num_pages=40, page_size=16, hash_block_size=32,
        max_batch_size=2, max_seq_len=128, prefill_buckets=(16, 32, 128),
        decode_horizon=4, **kw)
    return InferenceEngine(cfg)


def _greedy(engine, prompt, n=6):
    col = Collector()
    req = EngineRequest(service_request_id="r0", token_ids=list(prompt),
                        sampling=SamplingParams(max_tokens=n,
                                                temperature=0.0),
                        on_output=col)
    run_requests(engine, [req])
    return col.tokens


PROMPT = [7, 11, 13, 17, 19, 23, 29, 31, 37, 41]


class TestPallasEngineRouting:
    def test_kernel_decode_matches_default(self, monkeypatch):
        """decode_multi through the kernel: it reads the pool the append
        just wrote in place, layer by scalar-prefetch index."""
        baseline = _greedy(_pallas_capable_engine(), PROMPT)
        assert len(baseline) == 6
        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        engine = _pallas_capable_engine()
        assert _greedy(engine, PROMPT) == baseline
        assert engine.stats()["attention_paths"]["decode_multi"] == {
            "paged_attention": "pallas"}

    def test_kernel_prefill_serves_cold_and_prefix_hit_alike(self,
                                                             monkeypatch):
        """`prefill_install` through the prefill kernel: a cold admission
        (prefix 0) and one that finds two hash blocks cached (a 64-token
        prefix, the suffix in a smaller bucket) serve the tokens the XLA
        form serves, in float32, and the record says which form ran."""
        shared = list(range(3, 67))                # two hash blocks of 32
        prompts = [shared + [71, 73, 79], shared + [83, 89, 97, 101, 103]]

        def serve(engine):
            out = [_greedy(engine, p, n=5) for p in prompts]
            hits = engine.telemetry.counters["prefix_hit_tokens"]
            return out, hits

        baseline, hits = serve(_pallas_capable_engine())
        assert hits == 64 and all(len(t) == 5 for t in baseline)
        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        engine = _pallas_capable_engine()
        assert serve(engine) == (baseline, 64)
        paths = engine.stats()["attention_paths"]
        assert paths["prefill_install"] == {"prefill_attention": "pallas"}
        assert paths["decode_multi"] == {"paged_attention": "pallas"}
