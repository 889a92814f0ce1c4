"""M-RoPE parity drills for the Qwen2-VL LM stack.

Hermetic HF-parity: a synthetic checkpoint is loaded BOTH into our
qwen2_vl family and into transformers' Qwen2VLForConditionalGeneration;
position ids and prefill logits for an image-bearing sequence must
match the HF reference implementation (reference BASELINE config 5,
`multimodal.proto` in xllm_service proto surface).
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from xllm_service_tpu.models import llama as _llama
from xllm_service_tpu.models.loader import load_hf_qwen2_vl_safetensors
from xllm_service_tpu.models.qwen2_vl import (
    mrope_positions,
    prefill_forward,
    tiny_vl_config,
)

from test_loader import make_hf_qwen2_vl_checkpoint

IMG = 500   # placeholder token id (within tiny vocab)


def _tokens_with_image():
    # 498/499 = vision_start/end markers (HF's get_rope_index locates
    # image runs via vision_start_token_id; both sides treat the markers
    # themselves as ordinary text positions).
    return (list(range(30, 34)) + [498] + [IMG] * 4 + [499]
            + list(range(40, 45)))


class TestMropePositions:
    def test_text_only_is_sequential(self):
        pos, delta = mrope_positions(list(range(10, 20)), IMG)
        np.testing.assert_array_equal(pos, np.arange(10)[:, None].repeat(3, 1))
        assert delta == 0

    def test_image_grid_sweep(self):
        # 5 text (incl. vision_start) + 2x2 image grid + 6 text.
        pos, delta = mrope_positions(_tokens_with_image(), IMG)
        # Text prefix: all axes sequential 0..4.
        np.testing.assert_array_equal(pos[:5], np.arange(5)[:, None].repeat(3, 1))
        # Image run: t constant at 5; h rows 0,0,1,1; w cols 0,1,0,1.
        np.testing.assert_array_equal(pos[5:9, 0], [5, 5, 5, 5])
        np.testing.assert_array_equal(pos[5:9, 1], [5, 5, 6, 6])
        np.testing.assert_array_equal(pos[5:9, 2], [5, 6, 5, 6])
        # Text suffix resumes at max+1 = 7.
        np.testing.assert_array_equal(pos[9:, 0], np.arange(7, 13))
        # delta = next position (13) - seq_len (15).
        assert delta == 13 - 15

    def test_matches_hf_get_rope_index(self):
        torch = pytest.importorskip("torch")
        transformers = pytest.importorskip("transformers")
        from transformers import Qwen2VLConfig
        from transformers.models.qwen2_vl.modeling_qwen2_vl import (
            Qwen2VLForConditionalGeneration,
        )

        hf_cfg = Qwen2VLConfig(
            text_config=dict(
                vocab_size=512, hidden_size=128, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, rope_theta=500000.0,
                max_position_embeddings=512,
                rope_scaling={"type": "mrope", "mrope_section": [4, 6, 6]},
                tie_word_embeddings=False),
            vision_config=dict(embed_dim=64, depth=2, num_heads=4,
                               hidden_size=128, patch_size=14,
                               spatial_merge_size=1, temporal_patch_size=1,
                               in_channels=3),
            image_token_id=IMG, vision_start_token_id=498,
            vision_end_token_id=499, video_token_id=501)
        model = Qwen2VLForConditionalGeneration(hf_cfg)

        toks = _tokens_with_image()
        ids = torch.tensor([toks])
        hf_pos, hf_delta = model.model.get_rope_index(
            ids, image_grid_thw=torch.tensor([[1, 2, 2]]))
        ours, delta = mrope_positions(toks, IMG)
        np.testing.assert_array_equal(
            np.asarray(hf_pos[:, 0, :]), ours.T)
        assert int(hf_delta.reshape(-1)[0]) == delta


class TestMropeLogitsParity:
    def test_prefill_logits_match_hf(self, tmp_path):
        torch = pytest.importorskip("torch")
        from transformers import Qwen2VLConfig
        from transformers.models.qwen2_vl.modeling_qwen2_vl import (
            Qwen2VLForConditionalGeneration,
        )

        cfg = tiny_vl_config(dtype=jnp.float32, image_token_id=IMG)
        tensors = make_hf_qwen2_vl_checkpoint(tmp_path, cfg)
        params = load_hf_qwen2_vl_safetensors(tmp_path, cfg)

        hf_cfg = Qwen2VLConfig(
            text_config=dict(
                vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
                intermediate_size=cfg.ffn_size,
                num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                rms_norm_eps=cfg.rms_eps, max_position_embeddings=512,
                rope_scaling={"type": "mrope",
                              "mrope_section": list(cfg.mrope_section)},
                tie_word_embeddings=False),
            vision_config=dict(embed_dim=64, depth=2, num_heads=4,
                               hidden_size=cfg.hidden_size, patch_size=14,
                               spatial_merge_size=1, temporal_patch_size=1,
                               in_channels=3),
            image_token_id=IMG, vision_start_token_id=498,
            vision_end_token_id=499, video_token_id=501)
        model = Qwen2VLForConditionalGeneration(hf_cfg)
        sd = {}
        for k, v in tensors.items():
            if k.startswith("model."):
                sd["model.language_model." + k[len("model."):]] = \
                    torch.from_numpy(v)
            elif k.startswith("visual."):
                sd["model.visual." + k[len("visual."):]] = \
                    torch.from_numpy(v)
            else:
                sd[k] = torch.from_numpy(v)
        missing, unexpected = model.load_state_dict(sd, strict=False)
        # Only non-persistent buffers may be absent.
        assert not [m for m in missing if "inv_freq" not in m], missing
        model.eval()

        toks = _tokens_with_image()
        S = len(toks)
        pos3, _ = mrope_positions(toks, IMG)
        rng = np.random.default_rng(3)
        mm = rng.normal(size=(4, cfg.hidden_size)).astype(np.float32) * 0.1

        # Ours: family prefill (splices mm into placeholders) over a tiny
        # paged pool; last-token logits.
        n_pages, ps = 8, 16
        kv = jnp.zeros((cfg.num_layers, 2, n_pages, cfg.num_kv_heads, ps,
                        cfg.head_dim), jnp.float32)
        pt = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        logits, _ = prefill_forward(
            params, cfg, jnp.asarray([toks]), jnp.asarray(pos3)[None],
            kv, pt, jnp.asarray([0]), jnp.asarray([S]),
            mm_embeds=jnp.asarray(mm)[None])
        ours = np.asarray(logits[0], np.float32)

        # HF: same embeddings spliced by hand, text stack + lm_head.
        with torch.no_grad():
            ids = torch.tensor([toks])
            emb = model.model.language_model.embed_tokens(ids)
            is_img = ids == IMG
            emb[is_img] = torch.from_numpy(mm)
            hf_pos = torch.from_numpy(pos3.T.astype(np.int64))[:, None, :]
            out = model.model.language_model(
                inputs_embeds=emb, position_ids=hf_pos)
            hf_logits = model.lm_head(out.last_hidden_state)[0, -1]
        np.testing.assert_allclose(ours, hf_logits.numpy(),
                                   rtol=2e-3, atol=2e-3)


class TestMropePrefixCache:
    def test_text_only_vl_prefix_cache_same_output(self):
        """Text-only prompts on a VL engine use the prefix cache (only
        image-bearing sequences are excluded); the cached-prefix install
        uploads M-RoPE ids for the SUFFIX slice, which must compose with
        the matched prefix to the same greedy stream."""
        import threading

        from xllm_service_tpu.common.request import SamplingParams
        from xllm_service_tpu.engine.config import EngineConfig
        from xllm_service_tpu.engine.engine import (EngineRequest,
                                                    InferenceEngine)

        cfg = tiny_vl_config(dtype=jnp.float32, max_context_len=256,
                             image_token_id=IMG)
        engine = InferenceEngine(EngineConfig(
            model_id="tiny-vl", model_family="qwen2_vl", model=cfg,
            num_pages=32, page_size=16, hash_block_size=32,
            max_batch_size=2, max_seq_len=128, prefill_buckets=(64, 128)))
        engine.start()
        prompt = list(range(10, 75))   # 65 tokens: 2 hash blocks + tail

        def run_one(tag):
            outs, done = [], threading.Event()

            def cb(out):
                for s in out.outputs:
                    outs.extend(s.token_ids)
                if out.finished:
                    done.set()

            engine.submit(EngineRequest(
                tag, token_ids=list(prompt),
                sampling=SamplingParams(max_tokens=6, temperature=0.0,
                                        ignore_eos=True), on_output=cb))
            assert done.wait(60)
            return outs

        first = run_one("vlpc-1")
        stats = engine.stats()
        assert stats["cached_blocks"] > 0     # blocks donated
        # The second run must actually HIT the cache (not just happen to
        # produce the same stream through a full prefill).
        real_match = engine.page_mgr.match_prefix
        hits = []

        def spy(tokens, **kw):
            res = real_match(tokens, **kw)
            hits.append(res[0])
            return res

        engine.page_mgr.match_prefix = spy
        second = run_one("vlpc-2")            # matches the cached prefix
        engine.stop()
        assert hits and hits[0] > 0, "prefix cache was not hit"
        assert first == second


class TestEngineDecodeDelta:
    def test_engine_greedy_matches_full_recompute(self):
        """The engine decodes with 1D positions + the per-slot M-RoPE
        delta; a full per-step prompt re-prefill with freshly computed
        3D position ids is the ground truth. Greedy tokens must match —
        this is exactly what breaks if the delta install/clear is wrong
        (an image grid leaves delta != 0)."""
        import threading

        from xllm_service_tpu.common.request import SamplingParams
        from xllm_service_tpu.engine.config import EngineConfig
        from xllm_service_tpu.engine.engine import (EngineRequest,
                                                    InferenceEngine)

        cfg = tiny_vl_config(dtype=jnp.float32, max_context_len=256,
                             image_token_id=IMG)
        ecfg = EngineConfig(
            model_id="tiny-vl", model_family="qwen2_vl", model=cfg,
            num_pages=32, page_size=16, hash_block_size=32,
            max_batch_size=2, max_seq_len=128, prefill_buckets=(64, 128),
            decode_horizon=2)
        engine = InferenceEngine(ecfg)
        rng = np.random.default_rng(7)
        mm = rng.normal(size=(4, cfg.hidden_size)).astype(np.float32)
        prompt = _tokens_with_image()
        n_new = 6

        outs = []
        done = threading.Event()

        def on_output(out):
            for s in out.outputs:
                outs.extend(s.token_ids)
            if out.finished:
                done.set()

        engine.submit(EngineRequest(
            "mrope-e2e", token_ids=list(prompt),
            sampling=SamplingParams(max_tokens=n_new, temperature=0.0,
                                    ignore_eos=True),
            on_output=on_output, mm_embeds=mm))
        engine.start()
        assert done.wait(60)
        engine.stop()
        assert len(outs) == n_new

        # Ground truth: re-prefill prompt+generated each step with fresh
        # 3D position ids (no paged state, no delta shortcut). Padded to
        # ONE fixed bucket so all steps share a single compiled program.
        params = engine.params
        seq = list(prompt)
        S_max = len(prompt) + n_new
        for step in range(n_new):
            pos3, _ = mrope_positions(seq, IMG)
            S = len(seq)
            pos_pad = np.zeros((S_max, 3), np.int32)
            pos_pad[:S] = pos3
            padded = seq + [0] * (S_max - S)
            kv = jnp.zeros((cfg.num_layers, 2, 16, cfg.num_kv_heads, 16,
                            cfg.head_dim), jnp.float32)
            pt = jnp.asarray([list(range(8))], jnp.int32)
            logits, _ = prefill_forward(
                params, cfg, jnp.asarray([padded]),
                jnp.asarray(pos_pad)[None],
                kv, pt, jnp.asarray([0]), jnp.asarray([S]),
                mm_embeds=jnp.asarray(mm)[None])
            nxt = int(np.argmax(np.asarray(logits[0])))
            assert nxt == outs[step], (step, nxt, outs)
            seq.append(nxt)
