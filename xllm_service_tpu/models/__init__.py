"""Model zoo for the TPU engine plane (functional JAX).

Families mirror the reference's benchmark configs (BASELINE.json): Llama-3
(llama.py), Qwen2/2.5 (qwen2.py — llama family with qkv bias), DeepSeek-V2
style MoE (deepseek_moe.py — expert-parallel decode), Qwen2-VL
(qwen2_vl.py — vision encoder + LM for EPD), Gemma/Gemma-2 (gemma.py —
GeGLU, embed scaling, unit-offset norms, logit softcap), Mixtral
(mixtral.py — no-shared-expert top-2 MoE).

All models share one contract (base.py): stacked-layer parameter pytrees
(`lax.scan` over layers), `prefill_forward` writing paged KV, and
`decode_forward` reading via paged attention.
"""

from .base import ModelConfig, ModelFamily, get_model_family, register_model_family

__all__ = ["ModelConfig", "ModelFamily", "get_model_family",
           "register_model_family"]
