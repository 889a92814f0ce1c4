"""Map a real HF model directory (config.json) onto a ModelConfig +
loader, so a published checkpoint boots without hand-written shape
tables.

The reference fleet boots directly from HF model dirs
(`/root/reference/docs/en/getting_started.md:73-90` passes a model path
to every engine); this module is the TPU framework's equivalent entry:

    cfg = model_config_from_hf(model_dir)
    params = load_checkpoint(model_dir, cfg)

Families map to the registered model families (models/__init__.py):
llama / qwen2 (qkv-bias llama) / gemma2 / mixtral / deepseek_v2(.5) /
qwen2_vl / granitemoehybrid (dense: Mamba-2 + GQA) / brumby (power
retention in every layer). Anything else raises with the offending
model_type.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Callable

from .base import ModelConfig


def _read_config(ckpt_dir: str | Path) -> dict:
    p = Path(ckpt_dir) / "config.json"
    if not p.exists():
        raise FileNotFoundError(f"no config.json under {ckpt_dir}")
    return json.loads(p.read_text())


def _common(hf: dict) -> dict[str, Any]:
    heads = hf["num_attention_heads"]
    return dict(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        ffn_size=hf["intermediate_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_context_len=int(hf.get("max_position_embeddings", 8192)),
    )


def _granite_hybrid(hf: dict, ckpt_dir) -> dict[str, Any]:
    """Granite-4.0-H (models/granite_hybrid.py). What the family does not
    compute is refused here, with the reason, not approximated."""
    def refuse(why: str):
        raise ValueError(f"granitemoehybrid under {ckpt_dir}: {why}")

    if hf.get("num_local_experts", 0):
        refuse(f"num_local_experts={hf['num_local_experts']}: routed "
               "experts are not implemented in this family (dense only: "
               "the shared SwiGLU of width shared_intermediate_size)")
    if hf.get("mamba_n_groups", 1) != 1:
        refuse(f"mamba_n_groups={hf['mamba_n_groups']}: the state update "
               "computes one group of B and C")
    if hf.get("position_embedding_type", "nope") != "nope":
        refuse(f"position_embedding_type "
               f"{hf['position_embedding_type']!r}: the family applies no "
               "position embedding (nope)")
    if hf.get("attention_bias") or hf.get("mamba_proj_bias"):
        refuse("projection biases are not implemented")
    if not hf.get("mamba_conv_bias", True):
        refuse("a convolution without bias is not implemented")
    if not hf.get("tie_word_embeddings", False):
        refuse("an untied output head is not implemented")
    heads, d_head = hf["mamba_n_heads"], hf["mamba_d_head"]
    if heads * d_head != hf["mamba_expand"] * hf["hidden_size"]:
        refuse(f"mamba_n_heads x mamba_d_head = {heads * d_head} is not "
               f"mamba_expand x hidden_size")
    kw = _common(hf)
    kw.update(
        name="granite_hybrid",
        ffn_size=hf["shared_intermediate_size"],
        layer_types=tuple(hf["layer_types"]),
        ssm_heads=heads, ssm_head_dim=d_head,
        ssm_state=hf["mamba_d_state"], ssm_conv=hf["mamba_d_conv"],
        ssm_chunk=hf["mamba_chunk_size"],
        embed_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        attn_multiplier=float(hf["attention_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        # heads of 64 are outside the paged kernel's tiling (head_dim %
        # 128): the family holds them zero-padded to the lane width
        kv_held_dim=-(-kw["head_dim"] // 128) * 128)
    return kw


def _power_retention(hf: dict, ckpt_dir) -> dict[str, Any]:
    """Brumby (models/power_retention.py): Qwen3's keys, a power-retention
    mixer in every layer. What the family does not compute is refused."""
    def refuse(why: str):
        raise ValueError(f"brumby under {ckpt_dir}: {why}")

    if hf.get("attention_bias"):
        refuse("projection biases are not implemented")
    if hf.get("tie_word_embeddings", False):
        refuse("a tied output head is not implemented")
    if hf.get("rope_scaling"):
        refuse(f"rope_scaling {hf['rope_scaling']!r}: the family rotates by "
               "the plain half-split embedding")
    if hf.get("use_sliding_window") or hf.get("sliding_window"):
        refuse("a sliding window has no meaning for a retention layer")
    kw = _common(hf)
    if kw["num_heads"] % kw["num_kv_heads"] or kw["head_dim"] % 2:
        refuse(f"{kw['num_heads']} query heads over {kw['num_kv_heads']} KV "
               f"heads of {kw['head_dim']}: the state is shared by an "
               "integer group, folded along an even head size")
    kw.update(name="power_retention",
              layer_types=("retention",) * kw["num_layers"])
    return kw


def _deepseek(hf: dict, ckpt_dir) -> dict[str, Any]:
    """DeepSeek-V2 / -V3 (models/deepseek_moe.py): latent attention and
    routed experts. What the family does not compute is refused here, by
    the config's own key, not dropped."""
    mt = hf["model_type"]

    def refuse(why: str):
        raise ValueError(f"{mt} under {ckpt_dir}: {why}")

    if hf.get("q_lora_rank") is not None:
        refuse(f"q_lora_rank={hf['q_lora_rank']}: a low-rank query "
               "projection is not implemented (q_proj is one kernel)")
    for key in ("n_group", "topk_group"):
        if (hf.get(key) or 1) > 1:
            refuse(f"{key}={hf[key]}: group-limited routing is not "
                   "implemented (the experts are chosen over all of them)")
    if hf.get("rope_scaling") is not None:
        refuse(f"rope_scaling={hf['rope_scaling']!r}: scaled rotary "
               "embeddings are not implemented")
    scoring = hf.get("scoring_func", "softmax")
    method = hf.get("topk_method", "greedy")
    if scoring not in ("softmax", "sigmoid"):
        refuse(f"scoring_func {scoring!r}: softmax and sigmoid are "
               "implemented")
    if method not in ("greedy", "group_limited_greedy", "noaux_tc"):
        refuse(f"topk_method {method!r} is not implemented")
    kw = _common(hf)
    latent = hf["kv_lora_rank"] + hf["qk_rope_head_dim"]
    # MLA: the paged cache stores one [kv_lora_rank + rope] latent
    # per token — advertised as a single wide KV head (the engine's
    # pool layout; see deepseek_v2_lite_config), held at the next lane
    # multiple (576 -> 640) so that the paged kernel's tiling takes it.
    kw.update(
        name="deepseek_moe",
        num_kv_heads=1,
        head_dim=latent,
        kv_held_dim=-(-latent // 128) * 128,
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        num_experts=hf.get("n_routed_experts", 0),
        num_experts_per_token=hf.get("num_experts_per_tok", 2),
        num_shared_experts=hf.get("n_shared_experts", 0),
        moe_ffn_size=hf.get("moe_intermediate_size", 0),
        first_dense_layers=hf.get("first_k_dense_replace", 1),
        router_scoring=scoring,
        router_bias=method == "noaux_tc",
        router_norm_topk=bool(hf.get("norm_topk_prob", mt == "deepseek_v3")),
        routed_scale=float(hf.get("routed_scaling_factor", 1.0)),
        # V2's published code rotates adjacent pairs (as complex numbers);
        # V3's config says which
        rope_interleave=bool(hf.get("rope_interleave", True)))
    return kw


def model_config_from_hf(ckpt_dir: str | Path, *,
                         dtype=None,
                         max_context_len: int | None = None) -> ModelConfig:
    """Build a ModelConfig from an HF checkpoint dir's config.json.

    dtype/max_context_len override the checkpoint (serving usually wants
    bf16 and a bounded context regardless of what the config claims)."""
    hf = _read_config(ckpt_dir)
    mt = hf.get("model_type", "")

    if mt in ("llama", "qwen2"):
        kw = _common(hf)
        kw.update(name="llama" if mt == "llama" else "qwen2",
                  qkv_bias=(mt == "qwen2"))
    elif mt == "gemma2":
        kw = _common(hf)
        kw.update(
            name="gemma", act="gelu", embed_scale=True,
            rms_unit_offset=True, sandwich_norms=True,
            final_logit_softcap=float(
                hf.get("final_logit_softcapping") or 0.0),
            attn_logit_softcap=float(
                hf.get("attn_logit_softcapping") or 0.0),
            sliding_window=int(hf.get("sliding_window") or 0),
            # HF gemma-2 alternates local/global every other layer.
            sliding_window_pattern=2 if hf.get("sliding_window") else 0,
            query_pre_attn_scalar=float(
                hf.get("query_pre_attn_scalar") or 0.0))
    elif mt == "mixtral":
        kw = _common(hf)
        # HF's intermediate_size is the PER-EXPERT width; the MoE
        # forward reads moe_ffn_size (first_dense_layers=0: every
        # mixtral layer is sparse).
        kw.update(name="mixtral",
                  num_experts=hf["num_local_experts"],
                  num_experts_per_token=hf["num_experts_per_tok"],
                  moe_ffn_size=hf["intermediate_size"],
                  num_shared_experts=0, first_dense_layers=0)
    elif mt in ("deepseek_v2", "deepseek_v3"):
        kw = _deepseek(hf, ckpt_dir)
    elif mt == "qwen2_vl":
        from . import qwen2_vl  # noqa: F401 — registers the family
        from .base import VisionConfig
        kw = _common(hf)
        sec = (hf.get("rope_scaling") or {}).get("mrope_section") or ()
        vc = hf.get("vision_config") or {}
        merge = int(vc.get("spatial_merge_size", 2))
        patch = int(vc.get("patch_size", 14))
        # HF's vision_config carries no fixed image size (dynamic
        # resolution); the tower here runs the canonical 224px grid.
        image = 224
        kw.update(
            name="qwen2_vl", qkv_bias=True, mrope_section=tuple(sec),
            image_token_id=hf.get("image_token_id", 151655),
            vision=VisionConfig(
                image_size=image, patch_size=patch,
                hidden_size=int(vc.get("embed_dim",
                                       vc.get("hidden_size", 1280))),
                num_layers=int(vc.get("depth", vc.get("num_layers", 32))),
                num_heads=int(vc.get("num_heads", 16)),
                out_tokens=(image // patch // merge) ** 2,
                temporal_patch_size=int(vc.get("temporal_patch_size", 2)),
                spatial_merge_size=merge))
    elif mt == "granitemoehybrid":
        kw = _granite_hybrid(hf, ckpt_dir)
    elif mt == "brumby":
        kw = _power_retention(hf, ckpt_dir)
    else:
        raise ValueError(
            f"unsupported HF model_type {mt!r} under {ckpt_dir} — "
            f"supported: llama, qwen2, gemma2, mixtral, deepseek_v2/3, "
            f"qwen2_vl, granitemoehybrid, brumby")

    if dtype is not None:
        kw["dtype"] = dtype
    cfg = ModelConfig(**kw)
    if max_context_len is not None:
        cfg = dataclasses.replace(
            cfg, max_context_len=min(cfg.max_context_len, max_context_len))
    return cfg


def loader_for(cfg: ModelConfig) -> Callable:
    """The safetensors loader matching a config built above."""
    from . import loader as L
    return {
        "llama": L.load_hf_llama_safetensors,
        "qwen2": L.load_hf_llama_safetensors,
        "gemma": L.load_hf_llama_safetensors,
        "mixtral": L.load_hf_mixtral_safetensors,
        "deepseek_moe": L.load_hf_deepseek_safetensors,
        "qwen2_vl": L.load_hf_qwen2_vl_safetensors,
    }[cfg.name]


def load_checkpoint(ckpt_dir: str | Path, cfg: ModelConfig, mesh=None,
                    rules=None):
    """One-call load: pick the family loader and run it."""
    fn = loader_for(cfg)
    if mesh is not None:
        return fn(ckpt_dir, cfg, mesh=mesh, rules=rules)
    return fn(ckpt_dir, cfg)
