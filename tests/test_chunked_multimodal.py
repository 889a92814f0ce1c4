"""Chunked prefill + multimodal composition: a long VL prompt written
chunk-by-chunk (each chunk consuming its own slice of the visual
embeddings) must produce exactly the same output as whole-suffix prefill,
including placeholder runs that straddle chunk boundaries."""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.common.request import RequestOutput, SamplingParams
from xllm_service_tpu.engine.config import EngineConfig
from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
from xllm_service_tpu.models.qwen2_vl import tiny_vl_config

IMG_TOK = 100


def make_vl_engine(chunk=0) -> InferenceEngine:
    return InferenceEngine(EngineConfig(
        model_id="tiny-vl", model_family="qwen2_vl",
        model=tiny_vl_config(dtype=jnp.float32, max_context_len=256,
                             image_token_id=IMG_TOK),
        num_pages=64, page_size=16, hash_block_size=32,
        max_batch_size=2, max_seq_len=256, prefill_buckets=(16, 32, 64, 256),
        prefill_chunk_tokens=chunk))


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


def run_one(engine, prompt, mm, n=5):
    col = Collector()
    engine.submit(EngineRequest(
        "vl1", token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                ignore_eos=True),
        mm_embeds=mm, on_output=col))
    for _ in range(400):
        if col.done.is_set():
            break
        engine.step()
    assert col.done.is_set()
    return col.tokens


def make_prompt_and_mm(cfg):
    """~60-token prompt with two placeholder runs, one of which straddles
    the 16-token chunk boundary."""
    D = cfg.hidden_size
    n_mm = 6
    rng = np.random.default_rng(0)
    mm = rng.normal(size=(n_mm, D)).astype(np.float32)
    prompt = (list(range(10, 22)) + [IMG_TOK] * 3      # run crosses t=16
              + list(range(30, 55)) + [IMG_TOK] * 3
              + list(range(60, 77)))
    assert prompt.count(IMG_TOK) == n_mm
    return prompt, mm


class TestChunkedMultimodal:
    def test_chunked_matches_unchunked(self):
        base = make_vl_engine(0)
        prompt, mm = make_prompt_and_mm(base.cfg.model)
        want = run_one(base, prompt, mm)

        chunked = make_vl_engine(16)
        spy = {"chunks": 0}
        real = chunked._prefill_chunk

        def wrap(*a):
            spy["chunks"] += 1
            return real(*a)

        chunked._prefill_chunk = wrap
        got = run_one(chunked, prompt, mm)
        assert spy["chunks"] >= 2, "prompt was not actually chunked"
        assert got == want

    def test_warmup_covers_image_variant(self):
        """VL warmup must pre-compile the image-carrying program variant
        too (its mm operand is unit-padded, a different shape from the
        no-image dummy), and a post-warmup image request must match a
        cold engine's output (image variants stayed cold)."""
        cold = make_vl_engine(0)
        prompt, mm = make_prompt_and_mm(cold.cfg.model)
        want = run_one(cold, prompt, mm)

        import dataclasses
        warm = InferenceEngine(dataclasses.replace(
            make_vl_engine(0).cfg, warmup_programs=True))
        unit = max(1, warm.cfg.model.vision.out_tokens * 4)
        seen = set()
        real = warm._prefill_install

        def spy(params, dstate, packed, mm_arr):
            seen.add(mm_arr.shape[1])
            return real(params, dstate, packed, mm_arr)

        warm._prefill_install = spy
        warm._warmup_programs()
        assert {1, unit} <= seen, f"warmup mm widths: {seen}"
        assert run_one(warm, prompt, mm) == want

    def test_different_images_still_differ_when_chunked(self):
        engine = make_vl_engine(16)
        prompt, mm = make_prompt_and_mm(engine.cfg.model)
        out1 = run_one(engine, prompt, mm)
        mm2 = np.random.default_rng(9).normal(
            size=mm.shape).astype(np.float32)
        out2 = run_one(engine, [t + 1 if t < IMG_TOK else t
                                for t in prompt], mm2)
        # (different prompt+images -> overwhelmingly different tokens;
        # guards against the splice silently ignoring mm in chunks)
        assert out1 != out2
