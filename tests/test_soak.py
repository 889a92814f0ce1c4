"""Randomized engine soak: concurrent arrivals, cancellations, mixed
budgets/priorities/stop-tokens, online+offline — against the pipelined
decode/spec/admission paths. Asserts terminal-output and resource-return
invariants rather than exact streams (exactness is covered by the
targeted suites)."""

from __future__ import annotations

import threading
import time

import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.base import tiny_config


class Term:
    def __init__(self):
        self.tokens = 0
        self.finished = False
        self.status_ok = True
        self.finish_reason = ""
        self.done = threading.Event()

    def __call__(self, out):
        for s in out.outputs:
            self.tokens += len(s.token_ids)
            if s.finish_reason:
                self.finish_reason = s.finish_reason
        if out.status is not None and not out.status.ok():
            self.status_ok = False
        if out.finished:
            self.finished = True
            self.done.set()


def _soak(cfg: EngineConfig, seed: int, plen_hi: int = 60):
    rng = np.random.default_rng(seed)
    engine = InferenceEngine(cfg)
    engine.start()

    N = 36
    terms = [Term() for _ in range(N)]
    cancelled: set[int] = set()

    def feeder():
        for i in range(N):
            plen = int(rng.integers(4, plen_hi))
            max_tokens = int(rng.integers(1, 24))
            sp = SamplingParams(max_tokens=max_tokens,
                                temperature=0.0, ignore_eos=True)
            if rng.random() < 0.2:
                # Some requests may stop early on a token they generate.
                sp.stop_token_ids = [int(rng.integers(10, 200))]
            if rng.random() < 0.3:
                sp = SamplingParams(max_tokens=max_tokens,
                                    temperature=0.7,
                                    seed=int(rng.integers(0, 1 << 30)),
                                    ignore_eos=True)
            engine.submit(EngineRequest(
                f"soak-{i}",
                token_ids=[int(t) for t in rng.integers(5, 400, plen)],
                sampling=sp,
                offline=bool(rng.random() < 0.3),
                priority=int(rng.integers(0, 3)),
                on_output=terms[i]))
            if rng.random() < 0.15:
                victim = int(rng.integers(0, i + 1))
                cancelled.add(victim)
                engine.cancel(f"soak-{victim}")
            time.sleep(float(rng.random()) * 0.05)

    f = threading.Thread(target=feeder)
    f.start()
    f.join()

    deadline = time.monotonic() + 180
    for i, t in enumerate(terms):
        assert t.done.wait(max(1.0, deadline - time.monotonic())), \
            f"request {i} never reached a terminal output"
    engine.stop()

    for i, t in enumerate(terms):
        assert t.finished, i
        if i not in cancelled:
            assert t.status_ok, i
    # Every slot and page returned (prefix-cache pages are retained but
    # accounted as cached, not leaked).
    assert len(engine._running) == 0
    assert len(engine._prefillings) == 0
    assert sorted(engine._free_slots) == list(range(cfg.max_batch_size))
    assert engine._pending_decode is None
    assert engine._pending_spec is None
    st = engine.stats()
    assert st["waiting"] == 0
    return engine


def test_soak_random_workload():
    _soak(EngineConfig(
        model=tiny_config(dtype=jnp.float32, max_context_len=256),
        num_pages=48, page_size=16, hash_block_size=32,
        max_batch_size=4, max_seq_len=128,
        prefill_buckets=(32, 64, 128),
        decode_horizon=4, admission_horizon=2,
        speculate_k=3),                   # spec path on (llama family)
        seed=42)


def test_soak_with_sarathi_chunking():
    """Same randomized invariants with chunked prefill + mixed
    decode+chunk rides in the mix (spec stays on, so ride/spec path
    switching, cancels mid-ride, and preemption all interleave)."""
    engine = _soak(EngineConfig(
        model=tiny_config(dtype=jnp.float32, max_context_len=256),
        num_pages=48, page_size=16, hash_block_size=32,
        max_batch_size=4, max_seq_len=128,
        prefill_buckets=(32, 64, 128),
        decode_horizon=4, admission_horizon=2,
        speculate_k=3, prefill_chunk_tokens=32),
        seed=1234, plen_hi=100)
    assert engine.telemetry.counters["sarathi_rides"] > 0, \
        "soak never exercised the mixed decode+chunk path"
