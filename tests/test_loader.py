"""Checkpoint loading: synthetic HF safetensors round-trip + orbax.

Covers every family's HF layout: llama/qwen2
dense, DeepSeek-V2 MLA+MoE (kv_a/kv_b splits, expert stacks, layer-0
dense MLP), Mixtral (w1/w3/w2), and Qwen2-VL (vision tower + merger)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from xllm_service_tpu.models.base import get_model_family, tiny_config
from xllm_service_tpu.models.loader import (
    load_hf_deepseek_safetensors,
    load_hf_llama_safetensors,
    load_hf_mixtral_safetensors,
    load_hf_qwen2_vl_safetensors,
    load_params,
    save_params,
)


def make_hf_checkpoint(tmp_path, cfg, qkv_bias=False, lm_head=True, seed=0):
    """Write a synthetic HF-style llama checkpoint (2 shards)."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    D, L = cfg.hidden_size, cfg.num_layers
    Hq, Hkv, F = cfg.q_size, cfg.kv_size, cfg.ffn_size

    def t(*shape):
        return rng.normal(size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": t(cfg.vocab_size, D),
        "model.norm.weight": t(D),
    }
    if lm_head:
        tensors["lm_head.weight"] = t(cfg.vocab_size, D)
    for l in range(L):
        p = f"model.layers.{l}."
        tensors[p + "input_layernorm.weight"] = t(D)
        tensors[p + "self_attn.q_proj.weight"] = t(Hq, D)   # HF: [out, in]
        tensors[p + "self_attn.k_proj.weight"] = t(Hkv, D)
        tensors[p + "self_attn.v_proj.weight"] = t(Hkv, D)
        tensors[p + "self_attn.o_proj.weight"] = t(D, Hq)
        tensors[p + "post_attention_layernorm.weight"] = t(D)
        tensors[p + "mlp.gate_proj.weight"] = t(F, D)
        tensors[p + "mlp.up_proj.weight"] = t(F, D)
        tensors[p + "mlp.down_proj.weight"] = t(D, F)
        if qkv_bias:
            tensors[p + "self_attn.q_proj.bias"] = t(Hq)
            tensors[p + "self_attn.k_proj.bias"] = t(Hkv)
            tensors[p + "self_attn.v_proj.bias"] = t(Hkv)
        if cfg.sandwich_norms:  # gemma-2 checkpoint names
            tensors[p + "pre_feedforward_layernorm.weight"] = t(D)
            tensors[p + "post_feedforward_layernorm.weight"] = t(D)
    keys = sorted(tensors)
    half = len(keys) // 2
    save_file({k: tensors[k] for k in keys[:half]},
              str(tmp_path / "model-00001-of-00002.safetensors"))
    save_file({k: tensors[k] for k in keys[half:]},
              str(tmp_path / "model-00002-of-00002.safetensors"))
    return tensors


class TestHFLoader:
    def test_load_and_forward(self, tmp_path):
        cfg = tiny_config(dtype=jnp.float32)
        hf = make_hf_checkpoint(tmp_path, cfg)
        params = load_hf_llama_safetensors(tmp_path, cfg)
        # Shapes: stacked layers + transposed kernels.
        assert params["layers"]["q_proj"]["kernel"].shape == \
            (cfg.num_layers, cfg.hidden_size, cfg.q_size)
        np.testing.assert_allclose(
            np.asarray(params["layers"]["q_proj"]["kernel"][1]),
            hf["model.layers.1.self_attn.q_proj.weight"].T, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["embed"]["embedding"]),
            hf["model.embed_tokens.weight"], rtol=1e-6)
        # Forward runs.
        fam = get_model_family("llama")
        kv = jnp.zeros((cfg.num_layers, 2, 8, cfg.num_kv_heads, 16,
                        cfg.head_dim), cfg.dtype)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        logits, _ = fam.prefill_forward(
            params, cfg, jnp.zeros((1, 8), jnp.int32),
            jnp.arange(8)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([8], jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_qkv_bias_checkpoint(self, tmp_path):
        cfg = tiny_config(dtype=jnp.float32, qkv_bias=True)
        make_hf_checkpoint(tmp_path, cfg, qkv_bias=True)
        params = load_hf_llama_safetensors(tmp_path, cfg)
        assert params["layers"]["q_proj"]["bias"].shape == \
            (cfg.num_layers, cfg.q_size)

    def test_tied_checkpoint_without_lm_head(self, tmp_path):
        cfg = tiny_config(dtype=jnp.float32)
        hf = make_hf_checkpoint(tmp_path, cfg, lm_head=False)
        params = load_hf_llama_safetensors(tmp_path, cfg)
        np.testing.assert_allclose(
            np.asarray(params["lm_head"]["kernel"]),
            hf["model.embed_tokens.weight"].T, rtol=1e-6)

    def test_sharded_load(self, tmp_path):
        from xllm_service_tpu.models.llama import LLAMA_STACKED_RULES
        from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh

        cfg = tiny_config(dtype=jnp.float32)
        make_hf_checkpoint(tmp_path, cfg)
        mesh = build_mesh(MeshConfig(model=2), devices=jax.devices()[:2])
        params = load_hf_llama_safetensors(tmp_path, cfg, mesh=mesh,
                                           rules=LLAMA_STACKED_RULES)
        shard_shape = params["layers"]["q_proj"]["kernel"] \
            .addressable_shards[0].data.shape
        assert shard_shape[-1] == cfg.q_size // 2   # split on model axis

    def test_gemma2_checkpoint(self, tmp_path):
        """Gemma-2's sandwich norms load by their HF names and the loaded
        params serve a full prefill+decode (window/softcap path)."""
        from xllm_service_tpu.models.gemma import gemma2_tiny_config

        cfg = gemma2_tiny_config(dtype=jnp.float32)
        hf = make_hf_checkpoint(tmp_path, cfg, lm_head=False)
        params = load_hf_llama_safetensors(tmp_path, cfg)
        assert params["layers"]["pre_ffw_norm"]["scale"].shape == \
            (cfg.num_layers, cfg.hidden_size)
        np.testing.assert_allclose(
            np.asarray(params["layers"]["post_ffw_norm"]["scale"][2]),
            hf["model.layers.2.post_feedforward_layernorm.weight"],
            rtol=1e-6)
        fam = get_model_family("gemma")
        T = 12   # past the sliding window (8) so local layers mask
        kv = jnp.zeros((cfg.num_layers, 2, 8, cfg.num_kv_heads, 16,
                        cfg.head_dim), cfg.dtype)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        logits, kv = fam.prefill_forward(
            params, cfg, jnp.ones((1, T), jnp.int32),
            jnp.arange(T)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([T], jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits)))
        logits2, _ = fam.decode_forward(
            params, cfg, jnp.asarray([5], jnp.int32),
            jnp.asarray([T], jnp.int32), kv, pt,
            jnp.asarray([T + 1], jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits2)))

    def test_missing_layer_raises(self, tmp_path):
        from safetensors.numpy import save_file

        cfg = tiny_config(dtype=jnp.float32)
        tensors = {"model.embed_tokens.weight":
                   np.zeros((cfg.vocab_size, cfg.hidden_size), np.float32),
                   "model.norm.weight":
                   np.zeros((cfg.hidden_size,), np.float32),
                   "model.layers.0.self_attn.q_proj.weight":
                   np.zeros((cfg.q_size, cfg.hidden_size), np.float32)}
        save_file(tensors, str(tmp_path / "model.safetensors"))
        with pytest.raises(ValueError, match="missing layers"):
            load_hf_llama_safetensors(tmp_path, cfg)


class TestOrbaxRoundtrip:
    def test_save_load(self, tmp_path):
        cfg = tiny_config(dtype=jnp.float32)
        fam = get_model_family("llama")
        params = fam.init_params(cfg, jax.random.PRNGKey(0))
        save_params(params, tmp_path / "ckpt")
        back = load_params(tmp_path / "ckpt", cfg)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6), params, back)


# ----------------------------------------------- MoE / VL checkpoints ----
def make_hf_deepseek_checkpoint(tmp_path, cfg, seed=0):
    """Synthetic HF DeepSeek-V2 layout: MLA attention (kv_a/kv_b fused
    projections), layer 0 dense (first_k_dense_replace=1), MoE layers with
    routed + shared experts; with `cfg.router_bias` DeepSeek-V3's
    `e_score_correction_bias` beside each router."""
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    D, L, E = cfg.hidden_size, cfg.num_layers, cfg.num_experts
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    dc, dv = cfg.kv_lora_rank, cfg.v_head_dim
    Fe, Fs = cfg.moe_ffn_size, cfg.moe_ffn_size * cfg.num_shared_experts
    F = cfg.ffn_size

    def t(*shape):
        return rng.normal(size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": t(cfg.vocab_size, D),
        "model.norm.weight": t(D),
        "lm_head.weight": t(cfg.vocab_size, D),
    }
    for l in range(L):
        p = f"model.layers.{l}."
        tensors[p + "input_layernorm.weight"] = t(D)
        tensors[p + "post_attention_layernorm.weight"] = t(D)
        tensors[p + "self_attn.q_proj.weight"] = t(H * (dn + dr), D)
        tensors[p + "self_attn.kv_a_proj_with_mqa.weight"] = t(dc + dr, D)
        tensors[p + "self_attn.kv_a_layernorm.weight"] = t(dc)
        tensors[p + "self_attn.kv_b_proj.weight"] = t(H * (dn + dv), dc)
        tensors[p + "self_attn.o_proj.weight"] = t(D, H * dv)
        if l < cfg.first_dense_layers:
            tensors[p + "mlp.gate_proj.weight"] = t(F, D)
            tensors[p + "mlp.up_proj.weight"] = t(F, D)
            tensors[p + "mlp.down_proj.weight"] = t(D, F)
        else:
            tensors[p + "mlp.gate.weight"] = t(E, D)
            if cfg.router_bias:
                tensors[p + "mlp.gate.e_score_correction_bias"] = 0.2 * t(E)
            for e in range(E):
                ep = p + f"mlp.experts.{e}."
                tensors[ep + "gate_proj.weight"] = t(Fe, D)
                tensors[ep + "up_proj.weight"] = t(Fe, D)
                tensors[ep + "down_proj.weight"] = t(D, Fe)
            sp = p + "mlp.shared_experts."
            tensors[sp + "gate_proj.weight"] = t(Fs, D)
            tensors[sp + "up_proj.weight"] = t(Fs, D)
            tensors[sp + "down_proj.weight"] = t(D, Fs)
    keys = sorted(tensors)
    half = len(keys) // 2
    save_file({k: tensors[k] for k in keys[:half]},
              str(tmp_path / "model-00001-of-00002.safetensors"))
    save_file({k: tensors[k] for k in keys[half:]},
              str(tmp_path / "model-00002-of-00002.safetensors"))
    return tensors


def make_hf_mixtral_checkpoint(tmp_path, cfg, seed=0):
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    D, L, E = cfg.hidden_size, cfg.num_layers, cfg.num_experts
    Hq, Hkv, Fe = cfg.q_size, cfg.kv_size, cfg.moe_ffn_size

    def t(*shape):
        return rng.normal(size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": t(cfg.vocab_size, D),
        "model.norm.weight": t(D),
        "lm_head.weight": t(cfg.vocab_size, D),
    }
    for l in range(L):
        p = f"model.layers.{l}."
        tensors[p + "input_layernorm.weight"] = t(D)
        tensors[p + "post_attention_layernorm.weight"] = t(D)
        tensors[p + "self_attn.q_proj.weight"] = t(Hq, D)
        tensors[p + "self_attn.k_proj.weight"] = t(Hkv, D)
        tensors[p + "self_attn.v_proj.weight"] = t(Hkv, D)
        tensors[p + "self_attn.o_proj.weight"] = t(D, Hq)
        tensors[p + "block_sparse_moe.gate.weight"] = t(E, D)
        for e in range(E):
            ep = p + f"block_sparse_moe.experts.{e}."
            tensors[ep + "w1.weight"] = t(Fe, D)   # gate
            tensors[ep + "w2.weight"] = t(D, Fe)   # down
            tensors[ep + "w3.weight"] = t(Fe, D)   # up
    save_file(tensors, str(tmp_path / "model.safetensors"))
    return tensors


def make_hf_qwen2_vl_checkpoint(tmp_path, cfg, seed=0):
    from safetensors.numpy import save_file

    rng = np.random.default_rng(seed)
    v = cfg.vision
    D, L = cfg.hidden_size, cfg.num_layers
    Dv, Lv = v.hidden_size, v.num_layers
    Dm = Dv * v.spatial_merge_size ** 2
    Hq, Hkv, F = cfg.q_size, cfg.kv_size, cfg.ffn_size

    def t(*shape):
        return rng.normal(size=shape).astype(np.float32)

    tensors = {
        "model.embed_tokens.weight": t(cfg.vocab_size, D),
        "model.norm.weight": t(D),
        "lm_head.weight": t(cfg.vocab_size, D),
        "visual.patch_embed.proj.weight":
            t(Dv, 3, v.temporal_patch_size, v.patch_size, v.patch_size),
        "visual.merger.ln_q.weight": t(Dv),
        "visual.merger.ln_q.bias": t(Dv),
        "visual.merger.mlp.0.weight": t(Dm, Dm),
        "visual.merger.mlp.0.bias": t(Dm),
        "visual.merger.mlp.2.weight": t(D, Dm),
        "visual.merger.mlp.2.bias": t(D),
    }
    for l in range(L):
        p = f"model.layers.{l}."
        tensors[p + "input_layernorm.weight"] = t(D)
        tensors[p + "post_attention_layernorm.weight"] = t(D)
        tensors[p + "self_attn.q_proj.weight"] = t(Hq, D)
        tensors[p + "self_attn.q_proj.bias"] = t(Hq)
        tensors[p + "self_attn.k_proj.weight"] = t(Hkv, D)
        tensors[p + "self_attn.k_proj.bias"] = t(Hkv)
        tensors[p + "self_attn.v_proj.weight"] = t(Hkv, D)
        tensors[p + "self_attn.v_proj.bias"] = t(Hkv)
        tensors[p + "self_attn.o_proj.weight"] = t(D, Hq)
        tensors[p + "mlp.gate_proj.weight"] = t(F, D)
        tensors[p + "mlp.up_proj.weight"] = t(F, D)
        tensors[p + "mlp.down_proj.weight"] = t(D, F)
    for l in range(Lv):
        p = f"visual.blocks.{l}."
        tensors[p + "norm1.weight"] = t(Dv)
        tensors[p + "norm1.bias"] = t(Dv)
        tensors[p + "attn.qkv.weight"] = t(3 * Dv, Dv)
        tensors[p + "attn.qkv.bias"] = t(3 * Dv)
        tensors[p + "attn.proj.weight"] = t(Dv, Dv)
        tensors[p + "attn.proj.bias"] = t(Dv)
        tensors[p + "norm2.weight"] = t(Dv)
        tensors[p + "norm2.bias"] = t(Dv)
        tensors[p + "mlp.fc1.weight"] = t(4 * Dv, Dv)
        tensors[p + "mlp.fc1.bias"] = t(4 * Dv)
        tensors[p + "mlp.fc2.weight"] = t(Dv, 4 * Dv)
        tensors[p + "mlp.fc2.bias"] = t(Dv)
    save_file(tensors, str(tmp_path / "model.safetensors"))
    return tensors


class TestMoEAndVLLoaders:
    def test_deepseek_mla_moe_mapping_and_forward(self, tmp_path):
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config

        cfg = tiny_mla_config(dtype=jnp.float32, first_dense_layers=1,
                              num_layers=3)
        hf = make_hf_deepseek_checkpoint(tmp_path, cfg)
        params = load_hf_deepseek_safetensors(tmp_path, cfg)
        L, Ld = cfg.num_layers, cfg.first_dense_layers
        Lm = L - Ld
        dc, dr, dn = cfg.kv_lora_rank, cfg.qk_rope_head_dim, \
            cfg.qk_nope_head_dim
        H, dv = cfg.num_heads, cfg.v_head_dim
        # MLA split: kv_a rows -> kv_down | k_rope, transposed.
        kva = hf["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"]
        np.testing.assert_allclose(
            np.asarray(params["layers"]["kv_down"]["kernel"][1]),
            kva[:dc].T, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["layers"]["k_rope"]["kernel"][1]),
            kva[dc:dc + dr].T, rtol=1e-6)
        # kv_b -> absorbed k_up / v_up per head.
        kvb = hf["model.layers.2.self_attn.kv_b_proj.weight"] \
            .reshape(H, dn + dv, dc)
        np.testing.assert_allclose(
            np.asarray(params["layers"]["k_up"]["kernel"][2]),
            kvb[:, :dn, :], rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["layers"]["v_up"]["kernel"][2]),
            kvb[:, dn:, :].transpose(0, 2, 1), rtol=1e-6)
        # Router transpose (f32) + expert stack + dense layer 0 + shapes.
        np.testing.assert_allclose(
            np.asarray(params["moe"]["router"]["kernel"][0]),
            hf["model.layers.1.mlp.gate.weight"].T, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["moe"]["experts"]["down_proj"]["kernel"][1, 3]),
            hf["model.layers.2.mlp.experts.3.down_proj.weight"].T,
            rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["dense_mlp"]["gate_proj"]["kernel"][0]),
            hf["model.layers.0.mlp.gate_proj.weight"].T, rtol=1e-6)
        assert params["moe"]["experts"]["gate_proj"]["kernel"].shape == \
            (Lm, cfg.num_experts, cfg.hidden_size, cfg.moe_ffn_size)
        # Loaded params run the family forward.
        fam = get_model_family("deepseek_moe")
        kv = jnp.zeros((L, 2, 8, cfg.num_kv_heads, 16, cfg.head_dim),
                       cfg.dtype)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        logits, _ = fam.prefill_forward(
            params, cfg, jnp.ones((1, 8), jnp.int32),
            jnp.arange(8)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([8], jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_deepseek_served_matches_direct_forward(self, tmp_path):
        """The hermetic config-4 drill: loaded checkpoint served through
        the ENGINE == a by-hand greedy rollout with the same params."""
        from xllm_service_tpu.engine.config import EngineConfig
        from xllm_service_tpu.engine.engine import (EngineRequest,
                                                    InferenceEngine)
        from xllm_service_tpu.common.request import SamplingParams
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config
        import threading

        cfg = tiny_mla_config(dtype=jnp.float32, first_dense_layers=1,
                              num_layers=3)
        make_hf_deepseek_checkpoint(tmp_path, cfg)
        params = load_hf_deepseek_safetensors(tmp_path, cfg)
        fam = get_model_family("deepseek_moe")

        prompt = [(i * 7 + 3) % 200 + 5 for i in range(24)]
        n_new = 6
        # Direct rollout: prefill then greedy decode.
        kv = jnp.zeros((cfg.num_layers, 2, 16, cfg.num_kv_heads, 16,
                        cfg.head_dim), cfg.dtype)
        pt = jnp.arange(1, 9, dtype=jnp.int32)[None, :]
        logits, kv = fam.prefill_forward(
            params, cfg, jnp.asarray([prompt], jnp.int32),
            jnp.arange(len(prompt))[None, :], kv, pt,
            jnp.zeros((1,), jnp.int32),
            jnp.asarray([len(prompt)], jnp.int32))
        want = [int(jnp.argmax(logits[0]))]
        clen = len(prompt) + 1
        for _ in range(n_new - 1):
            logits, kv = fam.decode_forward(
                params, cfg, jnp.asarray([want[-1]], jnp.int32),
                jnp.asarray([clen - 1], jnp.int32), kv, pt,
                jnp.asarray([clen], jnp.int32))
            want.append(int(jnp.argmax(logits[0])))
            clen += 1

        engine = InferenceEngine(EngineConfig(
            model_id="ds", model_family="deepseek_moe", model=cfg,
            num_pages=16, page_size=16, hash_block_size=32,
            max_batch_size=2, max_seq_len=128, prefill_buckets=(32, 128)),
            params=params)
        got, done = [], threading.Event()

        def on_output(out):
            for s in out.outputs:
                got.extend(s.token_ids)
            if out.finished:
                done.set()

        engine.submit(EngineRequest(
            "r", token_ids=prompt,
            sampling=SamplingParams(max_tokens=n_new, temperature=0.0,
                                    ignore_eos=True),
            on_output=on_output))
        for _ in range(200):
            if done.is_set():
                break
            engine.step()
        assert done.is_set()
        assert got == want

    def test_mixtral_mapping_and_forward(self, tmp_path):
        from xllm_service_tpu.models.mixtral import mixtral_tiny_config

        cfg = mixtral_tiny_config(dtype=jnp.float32)
        hf = make_hf_mixtral_checkpoint(tmp_path, cfg)
        params = load_hf_mixtral_safetensors(tmp_path, cfg)
        # w1 -> gate, w3 -> up, w2 -> down (transposed, [L, E, ...]).
        np.testing.assert_allclose(
            np.asarray(params["moe"]["experts"]["gate_proj"]["kernel"][1, 2]),
            hf["model.layers.1.block_sparse_moe.experts.2.w1.weight"].T,
            rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["moe"]["experts"]["up_proj"]["kernel"][0, 3]),
            hf["model.layers.0.block_sparse_moe.experts.3.w3.weight"].T,
            rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["moe"]["router"]["kernel"][1]),
            hf["model.layers.1.block_sparse_moe.gate.weight"].T, rtol=1e-6)
        assert "shared" not in params["moe"]
        fam = get_model_family("mixtral")
        kv = jnp.zeros((cfg.num_layers, 2, 8, cfg.num_kv_heads, 16,
                        cfg.head_dim), cfg.dtype)
        pt = jnp.arange(4, dtype=jnp.int32)[None, :]
        logits, _ = fam.prefill_forward(
            params, cfg, jnp.ones((1, 8), jnp.int32),
            jnp.arange(8)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([8], jnp.int32))
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_qwen2_vl_mapping_and_encode(self, tmp_path):
        from xllm_service_tpu.models.base import VisionConfig
        from xllm_service_tpu.models.qwen2_vl import (encode_images,
                                                      tiny_vl_config)

        cfg = tiny_vl_config(
            dtype=jnp.float32,
            vision=VisionConfig(image_size=56, patch_size=14,
                                hidden_size=64, num_layers=2, num_heads=4,
                                out_tokens=4, temporal_patch_size=2,
                                spatial_merge_size=2))
        hf = make_hf_qwen2_vl_checkpoint(tmp_path, cfg)
        params = load_hf_qwen2_vl_safetensors(tmp_path, cfg)
        v = cfg.vision
        # Conv3d -> (c, t, ph, pw)-flattened linear.
        conv = hf["visual.patch_embed.proj.weight"]
        np.testing.assert_allclose(
            np.asarray(params["vision"]["patch_embed"]["kernel"]),
            conv.reshape(conv.shape[0], -1).T, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["vision"]["layers"]["qkv"]["kernel"][1]),
            hf["visual.blocks.1.attn.qkv.weight"].T, rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(params["vision"]["merger"]["fc2"]["kernel"]),
            hf["visual.merger.mlp.2.weight"].T, rtol=1e-6)
        # LM side has the qkv biases.
        assert params["layers"]["q_proj"]["bias"].shape == \
            (cfg.num_layers, cfg.q_size)
        # Encode runs at merged resolution: 56/14=4 grid, merge 2 -> 4.
        pixels = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 56, 56, 3)), jnp.float32)
        emb = encode_images(params, cfg, pixels)
        assert emb.shape == (2, v.out_tokens, cfg.hidden_size)
        assert bool(jnp.all(jnp.isfinite(emb)))

    def test_qwen25_vl_windowed_encode(self, tmp_path):
        """Qwen2.5-VL-style windowed attention: local blocks mask to
        non-overlapping windows, listed blocks stay global — and the
        window actually changes the output."""
        from xllm_service_tpu.models.base import VisionConfig
        from xllm_service_tpu.models.qwen2_vl import (encode_images,
                                                      tiny_vl_config)
        import dataclasses

        base_v = VisionConfig(image_size=56, patch_size=14, hidden_size=64,
                              num_layers=2, num_heads=4, out_tokens=4,
                              temporal_patch_size=2, spatial_merge_size=2)
        cfg = tiny_vl_config(dtype=jnp.float32, vision=base_v)
        make_hf_qwen2_vl_checkpoint(tmp_path, cfg)
        params = load_hf_qwen2_vl_safetensors(tmp_path, cfg)
        pixels = jnp.asarray(np.random.default_rng(1).normal(
            size=(1, 56, 56, 3)), jnp.float32)
        full = encode_images(params, cfg, pixels)
        wcfg = dataclasses.replace(cfg, vision=dataclasses.replace(
            base_v, window_size=2, fullatt_block_indexes=(1,)))
        windowed = encode_images(params, wcfg, pixels)
        assert windowed.shape == full.shape
        assert not np.allclose(np.asarray(windowed), np.asarray(full))
