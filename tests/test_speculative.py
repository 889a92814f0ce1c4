"""Speculative decoding (prompt-lookup drafts + one-forward verify):
greedy outputs must be IDENTICAL to the non-speculative engine; sampling
requests silently fall back to the normal decode path."""

import threading

import jax.numpy as jnp

from xllm_service_tpu.common.request import RequestOutput, SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.base import tiny_config


def make_engine(speculate_k=0, **kw) -> InferenceEngine:
    return InferenceEngine(EngineConfig(
        model=tiny_config(dtype=jnp.float32, max_context_len=512),
        num_pages=128, page_size=16, hash_block_size=32,
        max_batch_size=kw.pop("max_batch_size", 2), max_seq_len=512,
        prefill_buckets=(32, 64, 512), speculate_k=speculate_k, **kw))


class Collector:
    def __init__(self):
        self.outputs: list[RequestOutput] = []
        self.done = threading.Event()

    def __call__(self, out: RequestOutput) -> None:
        self.outputs.append(out)
        if out.finished:
            self.done.set()

    @property
    def tokens(self):
        return [t for o in self.outputs for s in o.outputs
                for t in s.token_ids]

    @property
    def finish_reason(self):
        for o in self.outputs:
            for s in o.outputs:
                if s.finish_reason:
                    return s.finish_reason
        return ""


def run_all(engine, reqs, max_steps=800):
    cols = []
    for r in reqs:
        engine.submit(r)
        cols.append(r.on_output)
    for _ in range(max_steps):
        if all(c.done.is_set() for c in cols):
            break
        engine.step()
    assert all(c.done.is_set() for c in cols)
    return cols


def greedy_req(sid, prompt, n=32, **kw):
    col = Collector()
    return EngineRequest(sid, token_ids=prompt,
                         sampling=SamplingParams(max_tokens=n,
                                                 temperature=0.0,
                                                 ignore_eos=True, **kw),
                         on_output=col)


REPETITIVE = [5, 6, 7, 8] * 10
VARIED = [(i * 13 + 2) % 400 + 10 for i in range(40)]


class TestSpeculativeDecoding:
    def test_greedy_identical_to_normal(self):
        base = run_all(make_engine(0), [greedy_req("a", REPETITIVE),
                                        greedy_req("b", VARIED)])
        spec = run_all(make_engine(4), [greedy_req("a", REPETITIVE),
                                        greedy_req("b", VARIED)])
        for b, s in zip(base, spec):
            assert s.tokens == b.tokens

    def test_spec_path_actually_used_and_accepts(self):
        engine = make_engine(4)
        calls = {"n": 0}
        real = engine._spec_multi

        def spy(*a):
            calls["n"] += 1
            return real(*a)

        engine._spec_multi = spy
        (col,) = run_all(engine, [greedy_req("a", REPETITIVE, n=96)])
        assert len(col.tokens) == 96
        # Each call runs speculate_cycles verify rounds; acceptance must
        # beat even the cycle count (96 tokens / 4-cycle calls).
        assert 0 < calls["n"] < 96 // engine.cfg.speculate_cycles

    def test_stop_token_respected(self):
        base_engine = make_engine(0)
        (b,) = run_all(base_engine, [greedy_req("a", REPETITIVE, n=8)])
        stop_tok = b.tokens[3]
        col = Collector()
        req = EngineRequest(
            "s", token_ids=REPETITIVE,
            sampling=SamplingParams(max_tokens=32, temperature=0.0,
                                    stop_token_ids=[stop_tok],
                                    ignore_eos=True),
            on_output=col)
        run_all(make_engine(4), [req])
        assert col.finish_reason == "stop"
        # Stop fires at the FIRST occurrence of the stop token in the
        # baseline stream (the repetitive prompt may repeat it well
        # before the index it was drawn from).
        k = b.tokens.index(stop_tok) + 1
        assert col.tokens == b.tokens[:k]

    def test_sampling_request_uses_normal_path(self):
        """With NO spec-eligible slot the plain decode horizon is used
        (same tokens/roundtrip without the dead verify positions)."""
        engine = make_engine(4)
        calls = {"n": 0}
        real = engine._spec_multi

        def spy(*a):
            calls["n"] += 1
            return real(*a)

        engine._spec_multi = spy
        col = Collector()
        req = EngineRequest(
            "s", token_ids=VARIED,
            sampling=SamplingParams(max_tokens=8, temperature=0.8, seed=7,
                                    ignore_eos=True),
            on_output=col)
        run_all(engine, [req])
        assert calls["n"] == 0
        assert len(col.tokens) == 8

    def test_mixed_batch_keeps_speculating_and_matches_normal(self):
        """One sampled request must NOT disable speculation for its
        greedy neighbor — and BOTH outputs must be
        byte-identical to the non-speculative engine (the sampled slot's
        step inside spec_multi uses the same fold_in(key, clens) RNG as
        decode_multi)."""
        def reqs():
            sampled = Collector()
            return [
                greedy_req("g", REPETITIVE, n=24),
                EngineRequest(
                    "s", token_ids=VARIED,
                    sampling=SamplingParams(max_tokens=24, temperature=0.8,
                                            seed=11, ignore_eos=True),
                    on_output=sampled),
            ]

        base = run_all(make_engine(0), reqs())
        engine = make_engine(4)
        calls = {"n": 0}
        real = engine._spec_multi

        def spy(*a):
            calls["n"] += 1
            return real(*a)

        engine._spec_multi = spy
        spec = run_all(engine, reqs())
        assert calls["n"] > 0, "spec path unused despite a greedy slot"
        for b, s in zip(base, spec):
            assert s.tokens == b.tokens

    def test_logprobs_request_in_mixed_batch(self):
        """A logprobs slot rides the spec program as a one-token-per-
        cycle slot with a full logprob payload, identical to the normal
        path's."""
        def reqs():
            lp = Collector()
            return [
                greedy_req("g", REPETITIVE, n=16),
                EngineRequest(
                    "l", token_ids=VARIED,
                    sampling=SamplingParams(max_tokens=16, temperature=0.0,
                                            logprobs=True, top_logprobs=3,
                                            ignore_eos=True),
                    on_output=lp),
            ]

        base = run_all(make_engine(0), reqs())
        spec = run_all(make_engine(4), reqs())
        for b, s in zip(base, spec):
            assert s.tokens == b.tokens
        blps = [lp for o in base[1].outputs for seq in o.outputs
                for lp in (seq.logprobs or [])]
        slps = [lp for o in spec[1].outputs for seq in o.outputs
                for lp in (seq.logprobs or [])]
        assert len(slps) == len(blps) > 0
        for b, s in zip(blps, slps):
            assert s.token_id == b.token_id
            assert abs(s.logprob - b.logprob) < 1e-4
            assert [t.token_id for t in s.top_logprobs] == \
                [t.token_id for t in b.top_logprobs]

    def test_chunked_prefill_history_feeds_drafts(self):
        """A chunked long prompt must still feed the draft search: the
        host repairs the device history row after install (chunk uploads
        carry no slot), so prompt-lookup matches across the WHOLE prompt
        — and greedy output stays identical to the unchunked engine."""
        prompt = REPETITIVE * 3          # 120 tokens, chunks of 32
        base = run_all(make_engine(4), [greedy_req("a", prompt, n=48)])
        chunked = make_engine(4, prefill_chunk_tokens=32)
        spy = {"cycles": 0, "emitted": 0}
        real = chunked._spec_multi

        def wrap(params, d, room, cycles):
            spy["cycles"] += cycles
            return real(params, d, room, cycles)

        chunked._spec_multi = wrap
        (col,) = run_all(chunked, [greedy_req("a", prompt, n=48)])
        assert col.tokens == base[0].tokens
        assert len(col.tokens) == 48
        # Acceptance: strictly fewer verify cycles than emitted tokens.
        assert 0 < spy["cycles"] < 48

    def test_budget_respected(self):
        """Spec can emit up to K+1 tokens per cycle; the budget cut must
        still be exact."""
        (c,) = run_all(make_engine(4), [greedy_req("a", REPETITIVE, n=5)])
        assert len(c.tokens) == 5
        assert c.finish_reason == "length"

    def test_budget_edge_does_not_corrupt_neighbor(self):
        """A sequence exhausting its budget mid-verify must not perturb a
        batch neighbor (overflow writes land in the garbage page / own
        slack pages, and the verify block is clamped to the remaining
        budget)."""
        base = run_all(make_engine(0), [greedy_req("a", REPETITIVE, n=3),
                                        greedy_req("b", VARIED, n=40)])
        spec = run_all(make_engine(4), [greedy_req("a", REPETITIVE, n=3),
                                        greedy_req("b", VARIED, n=40)])
        for b, s in zip(base, spec):
            assert s.tokens == b.tokens
