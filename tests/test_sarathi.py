"""Sarathi mixed decode+chunk engine path: while a
long prompt chunk-prefills, running decodes ride the SAME device program
(shared GEMMs). Output must be token-exact vs the plain interleaved
path, and the ride must actually engage."""

import jax.numpy as jnp

from xllm_service_tpu.common.request import SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.base import tiny_config

from test_engine import Collector, naive_greedy


def make_engine(chunk=32, **kw):
    return InferenceEngine(EngineConfig(
        model=tiny_config(dtype=jnp.float32, max_context_len=512),
        num_pages=96, page_size=16, hash_block_size=32,
        max_batch_size=4, max_seq_len=512,
        prefill_buckets=(32, 64, 512), prefill_chunk_tokens=chunk, **kw))


def _drive(engine):
    """Short decode running, then a long prompt chunk-prefills: the
    chunks should ride decode steps. Returns (short, long, rode)."""
    short, long_ = Collector(), Collector()
    engine.submit(EngineRequest(
        "short", token_ids=list(range(11, 21)),
        sampling=SamplingParams(max_tokens=40, temperature=0.0,
                                ignore_eos=True), on_output=short))
    engine.step()                      # short admitted + decoding
    engine.submit(EngineRequest(
        "long", token_ids=list(range(5, 245)),   # 240 tokens
        sampling=SamplingParams(max_tokens=4, temperature=0.0,
                                ignore_eos=True), on_output=long_))
    rode = 0
    for _ in range(300):
        engine.step()
        rode += bool(engine._rode_chunk)
        if short.done.is_set() and long_.done.is_set():
            break
    engine.stop()
    assert short.done.is_set() and long_.done.is_set()
    return short, long_, rode


def test_ride_engages_and_tokens_exact():
    plain = make_engine(chunk=0)
    want_short = naive_greedy(plain, list(range(11, 21)), 40)
    want_long = naive_greedy(plain, list(range(5, 245)), 4)

    engine = make_engine(chunk=32)
    short, long_, rode = _drive(engine)
    assert rode >= 2, "mixed decode+chunk path never engaged"
    assert short.tokens == want_short
    assert long_.tokens == want_long


def test_ride_respects_final_chunk_boundary():
    """The final <= chunk tokens must go through the normal install
    program (it samples the first token): _ride_chunk_args consumes at
    most remaining - C, and returns None once only the final chunk is
    left. Exercised directly so a regression (e.g. dropping the - C
    from rideable) fails here, not just via downstream parity."""
    engine = make_engine(chunk=32)
    col = Collector()
    engine.submit(EngineRequest(
        "warm", token_ids=list(range(3, 13)),
        sampling=SamplingParams(max_tokens=60, temperature=0.0,
                                ignore_eos=True), on_output=col))
    engine.step()
    long_ = Collector()
    engine.submit(EngineRequest(
        "long", token_ids=list(range(7, 107)),   # 100 tokens
        sampling=SamplingParams(max_tokens=2, temperature=0.0,
                                ignore_eos=True), on_output=long_))
    engine._admit()
    assert engine._prefillings
    st = engine._prefillings[0]
    C = engine.cfg.prefill_chunk_tokens
    seen_rides = 0
    while True:
        before = st["written"]
        ride = engine._ride_chunk_args(engine.cfg.decode_horizon)
        if ride is None:
            break
        seen_rides += 1
        # Each ride consumes at most one chunk and NEVER crosses into
        # the final chunk's territory.
        assert st["written"] - before <= C
        assert len(st["prompt"]) - st["written"] >= C
    assert seen_rides >= 1
    # Exactly the final chunk remains un-ridden.
    assert 0 < len(st["prompt"]) - st["written"] <= C
    # (Host bookkeeping only — the ride arrays were never dispatched, so
    # no generation assertions here; token parity with riding live is
    # test_ride_engages_and_tokens_exact's job.)
    engine.stop()


def test_pressure_rides_consume_four_chunk_spans():
    """With arrivals waiting, a truly-long prefill rides 4C per decode
    call (one fused step) instead of pacing one chunk at a time —
    token-exact either way."""
    plain = make_engine(chunk=0)
    want = [naive_greedy(plain, list(range(7 + i, 207 + i)), 3)
            for i in range(3)]

    engine = make_engine(chunk=16)   # 200-token prompts >> 4*16
    warm = Collector()
    engine.submit(EngineRequest(
        "warm", token_ids=list(range(2, 12)),
        sampling=SamplingParams(max_tokens=60, temperature=0.0,
                                ignore_eos=True), on_output=warm))
    engine.step()
    cols = [Collector() for _ in range(3)]
    for i, c in enumerate(cols):
        engine.submit(EngineRequest(
            f"L{i}", token_ids=list(range(7 + i, 207 + i)),
            sampling=SamplingParams(max_tokens=3, temperature=0.0,
                                    ignore_eos=True), on_output=c))
    big_rides = 0
    for _ in range(400):
        if engine._prefillings:
            st = engine._prefillings[0]
            before = st["written"]
            engine.step()
            if engine._rode_chunk and st["written"] - before > \
                    engine.cfg.prefill_chunk_tokens:
                big_rides += 1
        else:
            engine.step()
        if all(c.done.is_set() for c in cols):
            break
    engine.stop()
    assert big_rides >= 1, "pressure span never engaged"
    for i, c in enumerate(cols):
        assert c.tokens == want[i], i


def test_gemma2_rides_with_softcap():
    """The mixed program composes with the gemma-2 attention extras
    (score softcap, sliding window, query scale as static params) —
    token-exact vs the unchunked engine."""
    from xllm_service_tpu.models.gemma import gemma2_tiny_config

    def eng(chunk):
        return InferenceEngine(EngineConfig(
            model=gemma2_tiny_config(dtype=jnp.float32,
                                     max_context_len=512),
            model_family="gemma",
            num_pages=96, page_size=16, hash_block_size=32,
            max_batch_size=4, max_seq_len=512,
            prefill_buckets=(32, 64, 512), prefill_chunk_tokens=chunk))

    plain = eng(0)
    want_short = naive_greedy(plain, list(range(11, 21)), 30)
    want_long = naive_greedy(plain, list(range(5, 205)), 4)

    engine = eng(32)
    short, long_ = Collector(), Collector()
    engine.submit(EngineRequest(
        "short", token_ids=list(range(11, 21)),
        sampling=SamplingParams(max_tokens=30, temperature=0.0,
                                ignore_eos=True), on_output=short))
    engine.step()
    engine.submit(EngineRequest(
        "long", token_ids=list(range(5, 205)),
        sampling=SamplingParams(max_tokens=4, temperature=0.0,
                                ignore_eos=True), on_output=long_))
    rode = 0
    for _ in range(300):
        engine.step()
        rode += bool(engine._rode_chunk)
        if short.done.is_set() and long_.done.is_set():
            break
    engine.stop()
    assert rode >= 1, "gemma-2 never took the mixed path"
    assert short.tokens == want_short
    assert long_.tokens == want_long


def test_n_fanout_and_cancel_under_ride():
    """Cancellation of a riding prefill returns its pages/slot."""
    engine = make_engine(chunk=32)
    col = Collector()
    engine.submit(EngineRequest(
        "k", token_ids=list(range(4, 14)),
        sampling=SamplingParams(max_tokens=50, temperature=0.0,
                                ignore_eos=True), on_output=col))
    engine.step()
    lcol = Collector()
    engine.submit(EngineRequest(
        "lx", token_ids=list(range(9, 250)),
        sampling=SamplingParams(max_tokens=3, temperature=0.0,
                                ignore_eos=True), on_output=lcol))
    for _ in range(4):
        engine.step()
    assert engine._prefillings
    engine.cancel("lx")
    for _ in range(200):
        engine.step()
        if col.done.is_set():
            break
    engine.stop()
    assert not engine._prefillings
    assert lcol.done.is_set() and not lcol.outputs[-1].status.ok()
    assert len(col.tokens) == 50
    assert engine.page_mgr.num_free == engine.cfg.num_pages - 1
    assert len(engine._free_slots) == engine.cfg.max_batch_size
