"""Greedy-parity drill against real `transformers` models: a synthetic
HF checkpoint dir (config.json + safetensors + fast tokenizer) is loaded
BOTH by transformers (LlamaForCausalLM / Qwen2ForCausalLM) and by this
framework via models/hf_config → models/loader, then served through the
FULL stack (HTTP → master → agent → engine) by the real-checkpoint
drill's own run_drill(). Token-exact agreement proves framework output
== HF output on the shared weights — the same machinery
scripts/real_ckpt_drill.py points at a published checkpoint when one is
reachable (reference boots real model dirs,
docs/en/getting_started.md:73-90)."""

import importlib.util
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from xllm_service_tpu.models.base import tiny_config  # noqa: E402
from xllm_service_tpu.models.hf_config import (  # noqa: E402
    model_config_from_hf)

from test_loader import make_hf_checkpoint  # noqa: E402

spec = importlib.util.spec_from_file_location(
    "real_ckpt_drill", REPO / "scripts" / "real_ckpt_drill.py")
drill = importlib.util.module_from_spec(spec)
spec.loader.exec_module(drill)

VOCAB_WORDS = ["<pad>", "[UNK]", "the", "capital", "of", "france", "is",
               "paris", "a", "city", "hello", "world", "what", "up"]


def write_tokenizer(d: Path) -> None:
    from tokenizers import Tokenizer as HFTok
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import Whitespace

    vocab = {w: i for i, w in enumerate(VOCAB_WORDS)}
    t = HFTok(WordLevel(vocab, unk_token="[UNK]"))
    t.pre_tokenizer = Whitespace()
    t.save(str(d / "tokenizer.json"))
    (d / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "unk_token": "[UNK]", "pad_token": "<pad>",
        "add_bos_token": False,
    }))


def _write_index(d: Path, tensors: dict) -> None:
    """from_pretrained needs an index for two-shard safetensors."""
    half = set(sorted(tensors)[:len(tensors) // 2])
    (d / "model.safetensors.index.json").write_text(json.dumps({
        "metadata": {},
        "weight_map": {
            k: ("model-00001-of-00002.safetensors" if k in half
                else "model-00002-of-00002.safetensors")
            for k in tensors}}))


def make_model_dir(d: Path, model_type: str) -> Path:
    """Synthetic checkpoint transformers AND our loader both accept."""
    base = dict(
        rms_norm_eps=1e-5, max_position_embeddings=512,
        torch_dtype="float32", tie_word_embeddings=False)
    if model_type in ("llama", "qwen2"):
        cfg = tiny_config(dtype=jnp.float32,
                          qkv_bias=(model_type == "qwen2"))
        tensors = make_hf_checkpoint(d, cfg, qkv_bias=cfg.qkv_bias)
        _write_index(d, tensors)
        arch = {"llama": "LlamaForCausalLM",
                "qwen2": "Qwen2ForCausalLM"}[model_type]
        extra = {}
    elif model_type == "gemma2":
        from xllm_service_tpu.models.gemma import gemma2_tiny_config
        cfg = gemma2_tiny_config(dtype=jnp.float32, max_context_len=512,
                                 sliding_window=8)
        tensors = make_hf_checkpoint(d, cfg, lm_head=False)
        _write_index(d, tensors)
        arch = "Gemma2ForCausalLM"
        extra = {
            "hidden_activation": "gelu_pytorch_tanh",
            "query_pre_attn_scalar": cfg.query_pre_attn_scalar,
            "attn_logit_softcapping": cfg.attn_logit_softcap,
            "final_logit_softcapping": cfg.final_logit_softcap,
            "sliding_window": cfg.sliding_window,
        }
        base["tie_word_embeddings"] = True
    elif model_type == "mixtral":
        from xllm_service_tpu.models.mixtral import mixtral_tiny_config
        from test_loader import make_hf_mixtral_checkpoint
        cfg = mixtral_tiny_config(dtype=jnp.float32)
        make_hf_mixtral_checkpoint(d, cfg)   # single model.safetensors
        arch = "MixtralForCausalLM"
        extra = {
            "num_local_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_token,
        }
    elif model_type == "deepseek_v2":
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config
        from test_loader import make_hf_deepseek_checkpoint
        cfg = tiny_mla_config(dtype=jnp.float32, first_dense_layers=1)
        tensors = make_hf_deepseek_checkpoint(d, cfg)
        _write_index(d, tensors)
        arch = "DeepseekV2ForCausalLM"
        extra = {
            "q_lora_rank": None,         # plain q_proj (lite-style)
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "n_routed_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_token,
            "n_shared_experts": cfg.num_shared_experts,
            "moe_intermediate_size": cfg.moe_ffn_size,
            "first_k_dense_replace": cfg.first_dense_layers,
            "topk_method": "greedy", "norm_topk_prob": False,
            "routed_scaling_factor": 1.0,
            "moe_layer_freq": 1,
        }
    elif model_type == "deepseek_v3":
        # the DeepSeek-V3 router as published: a sigmoid of every logit, a
        # non-zero choice-only bias, the chosen normalised, a routed scale
        # that is not 1, and interleaved rotary pairs
        from xllm_service_tpu.models.deepseek_moe import tiny_mla_config
        from test_loader import make_hf_deepseek_checkpoint
        cfg = tiny_mla_config(
            dtype=jnp.float32, first_dense_layers=1, num_layers=3,
            num_experts=8, num_experts_per_token=3, num_shared_experts=2,
            router_scoring="sigmoid", router_bias=True,
            router_norm_topk=True, routed_scale=2.448, rope_interleave=True)
        tensors = make_hf_deepseek_checkpoint(d, cfg)
        _write_index(d, tensors)
        arch = "DeepseekV3ForCausalLM"
        extra = {
            "q_lora_rank": None, "rope_scaling": None,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "head_dim": cfg.qk_rope_head_dim,    # HF's rotary width
            "v_head_dim": cfg.v_head_dim,
            "n_routed_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.num_experts_per_token,
            "n_shared_experts": cfg.num_shared_experts,
            "moe_intermediate_size": cfg.moe_ffn_size,
            "first_k_dense_replace": cfg.first_dense_layers,
            "scoring_func": "sigmoid", "topk_method": "noaux_tc",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "routed_scaling_factor": cfg.routed_scale,
            "rope_interleave": True, "moe_layer_freq": 1,
        }
    else:
        raise AssertionError(model_type)
    base["rope_theta"] = cfg.rope_theta   # always the weights' theta
    ffn = cfg.moe_ffn_size if model_type == "mixtral" else cfg.ffn_size
    (d / "config.json").write_text(json.dumps({
        "model_type": model_type, "architectures": [arch],
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "intermediate_size": ffn,
        **base, **extra,
    }))
    write_tokenizer(d)
    return d


def test_hf_config_mapping(tmp_path):
    d = make_model_dir(tmp_path, "qwen2")
    cfg = model_config_from_hf(d, dtype=jnp.float32)
    ref = tiny_config(dtype=jnp.float32, qkv_bias=True)
    assert cfg.name == "qwen2" and cfg.qkv_bias
    for f in ("vocab_size", "hidden_size", "num_layers", "num_heads",
              "num_kv_heads", "head_dim", "ffn_size", "rope_theta"):
        assert getattr(cfg, f) == getattr(ref, f), f
    with pytest.raises(ValueError, match="model_type"):
        (tmp_path / "config.json").write_text(json.dumps(
            {"model_type": "mamba"}))
        model_config_from_hf(tmp_path)


@pytest.mark.parametrize("model_type", ["llama", "qwen2", "gemma2",
                                        "mixtral", "deepseek_v2",
                                        "deepseek_v3"])
def test_greedy_parity_full_stack(tmp_path, model_type):
    d = make_model_dir(tmp_path, model_type)
    out = drill.run_drill(str(d), prompt="the capital of france is",
                          max_new=12, max_context=256)
    assert out["ok"], out
    assert out["tokens_matched"] == out["tokens_total"] == 12
    assert out["model_type"] == {"gemma2": "gemma",
                                 "deepseek_v2": "deepseek_moe",
                                 "deepseek_v3": "deepseek_moe"}.get(
        model_type, model_type)


def _hf_logits(d: Path, tokens: list) -> np.ndarray:
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        str(d), torch_dtype=torch.float32).eval()
    with torch.no_grad():
        return model(torch.tensor([tokens])).logits[0].numpy()


@pytest.mark.parametrize("model_type", ["deepseek_v2", "deepseek_v3"])
def test_deepseek_logits_are_hfs_own(tmp_path, model_type):
    """The program's float32 forward against transformers' own
    `DeepseekV2ForCausalLM` / `DeepseekV3ForCausalLM` on one synthetic
    checkpoint, logit by logit: a prefill of 11 tokens through the paged
    cache, then 5 decode steps from it (the absorbed latent form with the
    interleaved rotation, the router's form with its choice-only bias,
    grouped experts). 2e-4 of logits whose spread is ~40 (random normal
    weights of variance 1): float32 sums in another order; a forward with
    the rotation as halves, the bias in the weights, or no routed scale
    reads 1 or more."""
    import jax
    from xllm_service_tpu.models import deepseek_moe as dm
    from xllm_service_tpu.models.hf_config import load_checkpoint

    d = make_model_dir(tmp_path, model_type)
    toks = [2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 3, 5, 9, 2]
    want = _hf_logits(d, toks)
    cfg = model_config_from_hf(d, dtype=jnp.float32)
    assert cfg.rope_interleave and cfg.kv_held_dim == 128
    if model_type == "deepseek_v3":
        assert (cfg.router_scoring, cfg.router_bias, cfg.router_norm_topk,
                cfg.routed_scale) == ("sigmoid", True, True, 2.448)
    else:
        assert (cfg.router_scoring, cfg.router_bias,
                cfg.router_norm_topk) == ("softmax", False, False)
    params = load_checkpoint(d, cfg)
    assert ("bias" in params["moe"]["router"]) == cfg.router_bias
    n = 11
    kv = jnp.zeros((cfg.num_layers, 2, 16, 1, 16, cfg.kv_head_dim),
                   jnp.float32)
    pt = jnp.arange(1, 5, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        lg, kv = dm.verify_forward(
            params, cfg, jnp.asarray([toks[:n] + [0] * 5]),
            jnp.arange(16)[None, :], kv, pt, jnp.zeros((1,), jnp.int32),
            jnp.asarray([n]))
        got = [np.asarray(lg[0, :n])]
        for i in range(n, len(toks)):
            lg, kv = dm.decode_forward(
                params, cfg, jnp.asarray([toks[i]]), jnp.asarray([i]), kv,
                pt, jnp.asarray([i + 1]))
            got.append(np.asarray(lg))
    got = np.concatenate(got)
    assert want.std() > 5
    assert np.max(np.abs(got - want)) < 2e-4 * want.std()


@pytest.mark.parametrize("key,value,why", [
    ("q_lora_rank", 64, "q_lora_rank=64"),
    ("n_group", 4, "n_group=4"),
    ("topk_group", 2, "topk_group=2"),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}, "rope_scaling="),
    ("scoring_func", "tanh", "scoring_func 'tanh'"),
])
def test_hf_config_refuses_what_deepseek_moe_does_not_compute(
        tmp_path, key, value, why):
    """What the family cannot state is refused by the config's own key,
    never dropped."""
    d = make_model_dir(tmp_path, "deepseek_v3")
    hf = json.loads((d / "config.json").read_text())
    assert model_config_from_hf(d).router_scoring == "sigmoid"
    (d / "config.json").write_text(json.dumps({**hf, key: value}))
    with pytest.raises(ValueError, match=why):
        model_config_from_hf(d)


def test_resolve_checkpoint_reports_unavailable(monkeypatch, tmp_path):
    monkeypatch.delenv("XLLM_REAL_CKPT", raising=False)
    monkeypatch.setenv("HF_HUB_OFFLINE", "1")
    ckpt, note = drill.resolve_checkpoint(None)
    # Either a cached snapshot exists (ok) or the attempt is documented.
    if ckpt is None:
        assert "unavailable" in note
    monkeypatch.setenv("XLLM_REAL_CKPT", str(tmp_path))  # no config.json
    ckpt, note = drill.resolve_checkpoint(None)
    assert ckpt is None and "config.json" in note
