"""Online/offline hybrid scheduling: admission priority + preemption with
lossless continuation (BASELINE config 3's hybrid half)."""

import jax.numpy as jnp

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.base import tiny_config

from test_engine import Collector, naive_greedy, run_requests


def tight_engine(num_pages=24, max_batch=2) -> InferenceEngine:
    """An engine with scarce KV pages so admission pressure is easy to hit."""
    return InferenceEngine(EngineConfig(
        model=tiny_config(dtype=jnp.float32, max_context_len=256),
        num_pages=num_pages, page_size=16, hash_block_size=32,
        max_batch_size=max_batch, max_seq_len=128,
        prefill_buckets=(32, 64, 128)))


class TestHybridScheduling:
    def test_online_admitted_before_offline(self):
        engine = tight_engine(num_pages=64, max_batch=1)  # one slot: serialize
        order = []

        def track(name, col):
            def cb(out):
                col(out)
                if out.finished:
                    order.append(name)
            return cb

        cols = {n: Collector() for n in ("off1", "off2", "on1")}
        sp = SamplingParams(max_tokens=2, temperature=0.0, ignore_eos=True)
        # Two offline queued first, then an online one.
        reqs = [
            EngineRequest("off1", token_ids=list(range(10)), sampling=sp,
                          offline=True, on_output=track("off1", cols["off1"])),
            EngineRequest("off2", token_ids=list(range(10, 20)), sampling=sp,
                          offline=True, on_output=track("off2", cols["off2"])),
            EngineRequest("on1", token_ids=list(range(20, 30)), sampling=sp,
                          on_output=track("on1", cols["on1"])),
        ]
        for r in reqs:
            engine.submit(r)
        while not all(c.done.is_set() for c in cols.values()):
            if not engine.step():
                break
        # off1 was already running (single slot); the online request must
        # jump ahead of off2 in the queue.
        assert order.index("on1") < order.index("off2")

    def test_preemption_resumes_losslessly(self):
        engine = tight_engine(num_pages=7, max_batch=2)
        # 6 usable pages. Offline reserves 3 (30 prompt + 12 new = 42 tok);
        # online needs 4 (60 prompt + 4 new) -> must preempt the offline.
        off_prompt = list(range(30, 60))
        on_prompt = list(range(100, 160))
        expected_off = naive_greedy(engine, off_prompt, 12)
        expected_on = naive_greedy(engine, on_prompt, 4)

        off_col, on_col = Collector(), Collector()
        engine.submit(EngineRequest(
            "off", token_ids=off_prompt,
            sampling=SamplingParams(max_tokens=12, temperature=0.0,
                                    ignore_eos=True),
            offline=True, on_output=off_col))
        # Let the offline request run a few tokens.
        for _ in range(4):
            engine.step()
        assert len(off_col.tokens) >= 2
        engine.submit(EngineRequest(
            "on", token_ids=on_prompt,
            sampling=SamplingParams(max_tokens=4, temperature=0.0,
                                    ignore_eos=True),
            on_output=on_col))
        while not (off_col.done.is_set() and on_col.done.is_set()):
            if not engine.step():
                break
        # Online served correctly.
        assert on_col.tokens == expected_on
        # Offline finished with the exact same stream an uninterrupted run
        # would have produced (continuation is lossless, no repeats).
        assert off_col.tokens == expected_off
        assert off_col.finish_reason == "length"
        # Engine drained cleanly, and the offline victim really was
        # preempted (not just co-scheduled).
        assert engine.telemetry.counters["preemptions"] >= 1
        assert engine.stats()["running"] == 0
