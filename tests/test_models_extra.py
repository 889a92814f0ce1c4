"""Qwen2 + DeepSeek-MoE family tests and ring-attention correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.models.base import get_model_family, tiny_config


def alloc_pages(cfg, num_pages, page_size=16):
    return jnp.zeros((cfg.num_layers, 2, num_pages, cfg.num_kv_heads,
                      page_size, cfg.head_dim), cfg.dtype)


class TestQwen2:
    def test_decode_matches_prefill_with_bias(self):
        cfg = tiny_config(dtype=jnp.float32, qkv_bias=True)
        fam = get_model_family("qwen2")
        params = fam.init_params(cfg, jax.random.PRNGKey(0))
        # Biases must exist and be non-degenerate in the pytree.
        assert "bias" in params["layers"]["q_proj"]
        T = 20
        toks = jax.random.randint(jax.random.PRNGKey(1), (1, T), 0,
                                  cfg.vocab_size)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        pos = jnp.arange(T)[None, :]
        kv = alloc_pages(cfg, 8)
        full, _ = fam.prefill_forward(params, cfg, toks, pos, kv, pt,
                                      jnp.zeros((1,), jnp.int32),
                                      jnp.array([T], jnp.int32))
        kv2 = alloc_pages(cfg, 8)
        _, kv2 = fam.prefill_forward(params, cfg, toks[:, :T - 1],
                                     pos[:, :T - 1], kv2, pt,
                                     jnp.zeros((1,), jnp.int32),
                                     jnp.array([T - 1], jnp.int32))
        dec, _ = fam.decode_forward(params, cfg, toks[:, T - 1],
                                    jnp.array([T - 1], jnp.int32), kv2, pt,
                                    jnp.array([T], jnp.int32))
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   rtol=2e-4, atol=2e-4)


class TestDeepSeekMoE:
    def _setup(self):
        from xllm_service_tpu.models.deepseek_moe import tiny_moe_config

        cfg = tiny_moe_config(dtype=jnp.float32)
        fam = get_model_family("deepseek_moe")
        params = fam.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, fam, params

    def test_decode_matches_prefill(self):
        cfg, fam, params = self._setup()
        T = 18
        toks = jax.random.randint(jax.random.PRNGKey(2), (1, T), 0,
                                  cfg.vocab_size)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        pos = jnp.arange(T)[None, :]
        kv = alloc_pages(cfg, 8)
        full, _ = fam.prefill_forward(params, cfg, toks, pos, kv, pt,
                                      jnp.zeros((1,), jnp.int32),
                                      jnp.array([T], jnp.int32))
        kv2 = alloc_pages(cfg, 8)
        _, kv2 = fam.prefill_forward(params, cfg, toks[:, :T - 1],
                                     pos[:, :T - 1], kv2, pt,
                                     jnp.zeros((1,), jnp.int32),
                                     jnp.array([T - 1], jnp.int32))
        dec, _ = fam.decode_forward(params, cfg, toks[:, T - 1],
                                    jnp.array([T - 1], jnp.int32), kv2, pt,
                                    jnp.array([T], jnp.int32))
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   rtol=5e-4, atol=5e-4)

    def test_router_sparsity(self):
        """Only top-k experts receive nonzero gates per token."""
        from xllm_service_tpu.models.deepseek_moe import _moe_mlp

        cfg, fam, params = self._setup()
        lp = jax.tree.map(lambda a: a[0], params["moe"])
        x = jax.random.normal(jax.random.PRNGKey(3), (5, cfg.hidden_size),
                              jnp.float32)
        logits = x @ lp["router"]["kernel"]
        topv, _ = jax.lax.top_k(logits, cfg.num_experts_per_token)
        assert topv.shape == (5, 2)
        out, counts = _moe_mlp(params["moe"], 0, x, cfg)
        assert out.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(out)))
        # five rows, two experts each of four: all four touched or fewer
        assert int(counts[0]) == 5 and 2 <= int(counts[1]) <= 4

    def test_expert_parallel_matches_single_device(self):
        cfg, fam, params = self._setup()
        from xllm_service_tpu.models.deepseek_moe import MOE_STACKED_RULES
        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh
        from xllm_service_tpu.parallel.sharding import shard_params

        mesh = build_mesh(MeshConfig(expert=4, model=2),
                          devices=jax.devices()[:8])
        sharded = shard_params(params, mesh, MOE_STACKED_RULES)
        T = 16
        toks = jax.random.randint(jax.random.PRNGKey(4), (1, T), 0,
                                  cfg.vocab_size)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        pos = jnp.arange(T)[None, :]
        args = (toks, pos, alloc_pages(cfg, 8), pt,
                jnp.zeros((1,), jnp.int32), jnp.array([T], jnp.int32))
        ref, _ = fam.prefill_forward(params, cfg, *args)
        with mesh:
            got, _ = jax.jit(
                lambda p, *a: fam.prefill_forward(p, cfg, *a))(sharded, *args)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-3, atol=2e-3)


class TestRingAttention:
    def test_matches_dense_causal(self):
        from xllm_service_tpu.ops.attention import prefill_attention
        from xllm_service_tpu.ops.ring_attention import ring_attention
        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(seq=4), devices=jax.devices()[:4])
        B, S, H, hd = 2, 64, 4, 32
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (B, S, H, hd), jnp.float32)
        k = jax.random.normal(k2, (B, S, H, hd), jnp.float32)
        v = jax.random.normal(k3, (B, S, H, hd), jnp.float32)

        ref = prefill_attention(q, k, v, None, None,
                                jnp.zeros((B, 1), jnp.int32),
                                jnp.zeros((B,), jnp.int32),
                                jnp.full((B,), S, jnp.int32))
        with mesh:
            got = ring_attention(q, k, v, mesh, seq_axis="seq")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_ring_degree_2(self):
        from xllm_service_tpu.ops.attention import prefill_attention
        from xllm_service_tpu.ops.ring_attention import ring_attention
        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(seq=2), devices=jax.devices()[:2])
        B, S, H, hd = 1, 32, 2, 32
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, hd),
                                     jnp.float32) for i in range(3))
        ref = prefill_attention(q, k, v, None, None,
                                jnp.zeros((B, 1), jnp.int32),
                                jnp.zeros((B,), jnp.int32),
                                jnp.full((B,), S, jnp.int32))
        with mesh:
            got = ring_attention(q, k, v, mesh, seq_axis="seq")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


class TestMLA:
    def _setup(self):
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config

        cfg = tiny_mla_config(dtype=jnp.float32)
        fam = get_model_family("deepseek_moe")
        params = fam.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, fam, params

    def test_cache_entry_is_compressed(self):
        cfg, fam, params = self._setup()
        # The pool stores one latent per token: n_kv=1, hd = dc + dr.
        assert cfg.num_kv_heads == 1
        assert cfg.head_dim == cfg.kv_lora_rank + cfg.qk_rope_head_dim
        assert "k_up" in params["layers"] and "kv_down" in params["layers"]

    def test_decode_matches_prefill(self):
        cfg, fam, params = self._setup()
        T = 21
        toks = jax.random.randint(jax.random.PRNGKey(5), (1, T), 0,
                                  cfg.vocab_size)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        pos = jnp.arange(T)[None, :]
        kv = alloc_pages(cfg, 8)
        full, _ = fam.prefill_forward(params, cfg, toks, pos, kv, pt,
                                      jnp.zeros((1,), jnp.int32),
                                      jnp.array([T], jnp.int32))
        kv2 = alloc_pages(cfg, 8)
        _, kv2 = fam.prefill_forward(params, cfg, toks[:, :T - 1],
                                     pos[:, :T - 1], kv2, pt,
                                     jnp.zeros((1,), jnp.int32),
                                     jnp.array([T - 1], jnp.int32))
        dec, _ = fam.decode_forward(params, cfg, toks[:, T - 1],
                                    jnp.array([T - 1], jnp.int32), kv2, pt,
                                    jnp.array([T], jnp.int32))
        np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                                   rtol=5e-4, atol=5e-4)

    def test_mla_engine_end_to_end(self):
        """MLA model through the continuous-batching engine."""
        from xllm_service_tpu.engine.config import EngineConfig
        from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
        from xllm_service_tpu.common.request import SamplingParams
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config
        from test_engine import Collector, run_requests

        cfg = EngineConfig(
            model_family="deepseek_moe",
            model=tiny_mla_config(dtype=jnp.float32, max_context_len=256),
            num_pages=32, page_size=16, hash_block_size=32,
            max_batch_size=2, max_seq_len=128, prefill_buckets=(32, 128))
        engine = InferenceEngine(cfg)
        col = Collector()
        run_requests(engine, [EngineRequest(
            "mla", token_ids=list(range(10, 40)),
            sampling=SamplingParams(max_tokens=4, temperature=0.0,
                                    ignore_eos=True), on_output=col)])
        assert len(col.tokens) == 4
        assert col.finish_reason == "length"

    def test_mla_sharded_matches_single_device(self):
        cfg, fam, params = self._setup()
        from xllm_service_tpu.models.deepseek_moe import MOE_STACKED_RULES
        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh
        from xllm_service_tpu.parallel.sharding import shard_params

        mesh = build_mesh(MeshConfig(expert=2, model=2),
                          devices=jax.devices()[:4])
        sharded = shard_params(params, mesh, MOE_STACKED_RULES)
        T = 16
        toks = jax.random.randint(jax.random.PRNGKey(6), (1, T), 0,
                                  cfg.vocab_size)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        pos = jnp.arange(T)[None, :]
        args = (toks, pos, alloc_pages(cfg, 8), pt,
                jnp.zeros((1,), jnp.int32), jnp.array([T], jnp.int32))
        ref, _ = fam.prefill_forward(params, cfg, *args)
        with mesh:
            got, _ = jax.jit(
                lambda p, *a: fam.prefill_forward(p, cfg, *a))(sharded, *args)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-3, atol=2e-3)


class TestMoeSpecAndEmbed:
    def test_moe_speculative_greedy_identical(self):
        """Speculative decoding over the MoE (MLA) family must equal its
        normal greedy output."""
        import threading

        from xllm_service_tpu.common.request import SamplingParams
        from xllm_service_tpu.engine.config import EngineConfig
        from xllm_service_tpu.engine.engine import (
            EngineRequest,
            InferenceEngine,
        )
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config

        def mk(spec):
            return InferenceEngine(EngineConfig(
                model_id="tiny-moe", model_family="deepseek_moe",
                model=tiny_mla_config(dtype=jnp.float32,
                                      max_context_len=256),
                num_pages=64, page_size=16, hash_block_size=32,
                max_batch_size=2, max_seq_len=256,
                prefill_buckets=(32, 64, 256), speculate_k=spec))

        def run(engine, prompt, n=16):
            done = threading.Event()
            toks = []

            def cb(out):
                toks.extend(t for s in out.outputs for t in s.token_ids)
                if out.finished:
                    done.set()

            engine.submit(EngineRequest(
                "m", token_ids=prompt,
                sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                        ignore_eos=True), on_output=cb))
            for _ in range(400):
                if done.is_set():
                    break
                engine.step()
            assert done.is_set()
            return toks

        prompt = [5, 6, 7, 8] * 8
        assert run(mk(4), prompt) == run(mk(0), prompt)

    def test_moe_embed_forward(self):
        from xllm_service_tpu.models.base import get_model_family
        from xllm_service_tpu.models.deepseek_moe import tiny_moe_config

        cfg = tiny_moe_config(dtype=jnp.float32)
        fam = get_model_family("deepseek_moe")
        params = fam.init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray([[5, 6, 7, 0], [9, 10, 0, 0]], jnp.int32)
        lens = jnp.asarray([3, 2], jnp.int32)
        v = fam.embed_forward(params, cfg, toks, lens)
        assert v.shape == (2, cfg.hidden_size)
        # Padding must not affect the pooled vector.
        toks2 = jnp.asarray([[5, 6, 7, 99], [9, 10, 42, 77]], jnp.int32)
        v2 = fam.embed_forward(params, cfg, toks2, lens)
        np.testing.assert_allclose(np.asarray(v), np.asarray(v2),
                                   rtol=1e-5, atol=1e-6)
