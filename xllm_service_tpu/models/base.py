"""Model contract shared by all families.

Engine-facing surface per family:
- ``init_params(cfg, rng) -> params`` — random init (benchmarks use random
  weights; checkpoint loading via orbax slots in behind the same pytree).
- ``prefill_forward(params, cfg, tokens, positions, kv_pages, page_tables,
  prefix_lens, seq_lens) -> (logits_last, kv_pages)`` — dense causal
  attention over the new suffix, K/V scattered into the paged pool.
- ``decode_forward(params, cfg, tokens, positions, kv_pages, page_tables,
  context_lens) -> (logits, kv_pages)`` — one step, paged attention.

Layers are stacked (leading L dim) and iterated with `lax.scan` — one
compiled layer body regardless of depth (fast compiles, XLA-friendly).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

# The blocks of a served program: every device op of a forward and of the
# engine's program tails is traced under exactly one of them, in every
# family, so that a device trace can be summed by block (docs/
# observability.md "By block"; the benchmark's `block.*` metrics).
BLOCKS = ("attn", "mlp", "moe", "ssm", "ret", "head", "sample")
BLOCK_PREFIX = "blk."


def block(name: str):
    """The scope of one block of the model: `with block("attn"): ...`.
    A name in HLO metadata and nothing else (no op is added or moved);
    scopes of a finer grain (`moe.route`, `mla.decode`, `ssm_update`, a
    kernel's jit) nest inside it."""
    if name not in BLOCKS:
        raise ValueError(f"no block {name!r} among {BLOCKS}")
    return jax.named_scope(BLOCK_PREFIX + name)


@dataclass(frozen=True)
class ModelConfig:
    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    num_kv_heads: int = 8
    head_dim: int = 128
    ffn_size: int = 5632
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    qkv_bias: bool = False          # Qwen2 family
    dtype: Any = jnp.bfloat16
    max_context_len: int = 8192
    # MLA — multi-head latent attention (deepseek family). kv_lora_rank>0
    # enables it; the paged cache then stores one [kv_lora_rank +
    # qk_rope_head_dim] latent per token (set num_kv_heads=1 and
    # head_dim=kv_lora_rank+qk_rope_head_dim so the engine's pool layout
    # matches).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE (deepseek family).
    num_experts: int = 0
    num_experts_per_token: int = 2
    num_shared_experts: int = 0
    moe_ffn_size: int = 0           # per-expert ffn width
    first_dense_layers: int = 1     # leading dense layers before MoE blocks
    # The router's form (models/deepseek_moe.py `_route`). `router_scoring`
    # "softmax" (Mixtral, DeepSeek-V2) or "sigmoid" (DeepSeek-V3: a sigmoid
    # of every logit); `router_bias`: a per-expert bias leaf beside the
    # router's kernel that enters the CHOICE of experts only, never their
    # weights (`topk_method` noaux_tc); `router_norm_topk`: the chosen
    # scores are normalised over the chosen; `routed_scale` multiplies
    # them. `rope_interleave`: the rotary part of q and k comes as adjacent
    # pairs (2i, 2i+1), not as two halves.
    router_scoring: str = "softmax"
    router_bias: bool = False
    router_norm_topk: bool = True
    routed_scale: float = 1.0
    rope_interleave: bool = False
    # Multimodal (qwen2_vl family).
    vision: Optional["VisionConfig"] = None
    image_token_id: int = 151655   # <|image_pad|> placeholder id
    # M-RoPE (qwen2_vl LM stack): per-axis (temporal, h, w) half-dim
    # rope sections, summing to head_dim // 2 (HF
    # `rope_scaling.mrope_section`). Empty = standard 1D rope.
    mrope_section: tuple = ()
    # Weight-only quantization ("" = off, "int8" = per-output-channel
    # int8 projections, models/quant.py). llama/qwen2 families.
    quant: str = ""
    # Gemma-family switches (models/gemma.py): GeGLU activation, embed
    # scaling by sqrt(hidden), RMSNorm computing (1 + w), final-logit
    # tanh softcap (0 = off). Honored by the shared llama layer body.
    act: str = "silu"
    embed_scale: bool = False
    rms_unit_offset: bool = False
    final_logit_softcap: float = 0.0
    # Gemma-2/3 attention extras (honored by the shared llama layer body;
    # the engine falls back to the XLA attention paths for these — the
    # Pallas/ring/CP kernels don't implement windowing or score capping):
    # - attn_logit_softcap: tanh-cap attention SCORES (gemma-2: 50.0);
    # - sliding_window + sliding_window_pattern N: layer l attends only to
    #   the trailing `sliding_window` positions unless (l % N) == N-1,
    #   which stays global (gemma-2: N=2 — even layers local, odd global);
    # - query_pre_attn_scalar: q scale = qpas**-0.5 instead of hd**-0.5;
    # - sandwich_norms: norm the attention/MLP OUTPUTS too (gemma-2's
    #   post_attention/pre_ffw/post_ffw layernorm arrangement).
    attn_logit_softcap: float = 0.0
    sliding_window: int = 0
    sliding_window_pattern: int = 0
    query_pre_attn_scalar: float = 0.0
    sandwich_norms: bool = False
    # Hybrid state-space models (models/granite_hybrid.py). `layer_types`
    # states each layer's mixer, "mamba" or "attention", in order; empty
    # means every layer is attention. The Mamba-2 widths: `ssm_heads` heads
    # of `ssm_head_dim` channels, one group of state size `ssm_state`, a
    # causal convolution of `ssm_conv` taps, prefill scanned in chunks of
    # `ssm_chunk`. The four Granite multipliers scale the embeddings, each
    # residual branch, the attention scores (0 = head_dim**-0.5) and divide
    # the logits. `kv_held_dim` is the width a key is held at in the pool
    # where that is not `head_dim` (0): a family whose heads are narrower
    # than the paged kernel's tiling states the padded width here.
    layer_types: tuple = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_chunk: int = 256
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attn_multiplier: float = 0.0
    logits_scaling: float = 1.0
    kv_held_dim: int = 0
    # Power retention (models/power_retention.py): `layer_types` names every
    # layer "retention" (no layer holds keys, so `kv_layers` is 0 and the
    # pool has no plane).

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values: the planes of the KV pool."""
        if not self.layer_types:
            return self.num_layers
        return sum(t == "attention" for t in self.layer_types)

    @property
    def kv_head_dim(self) -> int:
        """The width a key is held at in the pool: `head_dim` unless the
        configuration states another (`kv_held_dim`)."""
        return self.kv_held_dim or self.head_dim

    def layer_is_local(self, layer: int) -> bool:
        """True if `layer` uses sliding-window (local) attention."""
        n = self.sliding_window_pattern
        return (self.sliding_window > 0 and n > 0
                and (layer % n) != n - 1)

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim


@dataclass(frozen=True)
class VisionConfig:
    """Qwen2-VL-shaped vision tower (models/qwen2_vl.py, checkpoint
    layout `visual.*` — HF Qwen2VisionTransformer): Conv3d-equivalent
    patch embed with a temporal patch, 2D rotary position embedding over
    the (h, w) patch grid, LayerNorm blocks with fused qkv, QuickGELU
    MLP, and a spatial-merge PatchMerger projecting to the LM width.
    `window_size`/`fullatt_block_indexes` add Qwen2.5-VL-style windowed
    attention (local non-overlapping windows except the listed global
    blocks); window_size=0 keeps every block global (Qwen2-VL)."""

    # Defaults are mutually consistent with qwen2_vl.init_params'
    # invariant: out_tokens == (image_size/patch_size/spatial_merge)².
    image_size: int = 224
    patch_size: int = 14
    hidden_size: int = 1024
    num_layers: int = 4
    num_heads: int = 16
    out_tokens: int = 64            # visual tokens emitted per image
    temporal_patch_size: int = 2    # Qwen2-VL: 2 (image tiled over t)
    spatial_merge_size: int = 2     # Qwen2-VL: 2 (2x2 patch merge)
    rope_theta: float = 10000.0
    window_size: int = 0            # patches per window side (2.5-VL: 8)
    fullatt_block_indexes: tuple = ()


@dataclass
class ModelFamily:
    name: str
    init_params: Callable[..., Any]
    prefill_forward: Callable[..., Any]
    decode_forward: Callable[..., Any]
    sharding_rules: Any = None
    # Optional speculative-decoding verify: forward over a short
    # multi-token block returning per-position logits [B, S, V]. Families
    # without it simply never take the speculative path.
    verify_forward: Optional[Callable[..., Any]] = None
    # Optional text-embedding forward ([B, S] tokens -> [B, D] pooled);
    # families without it 501 /v1/embeddings like the reference.
    embed_forward: Optional[Callable[..., Any]] = None
    # Optional Sarathi-style mixed step: one forward that decodes the
    # running batch AND writes/attends a sub-chunk of ONE prefilling
    # sequence, sharing every projection/MLP GEMM (decode rows ride the
    # prefill's weight stream). Families without it interleave chunked
    # prefill and decode as separate programs.
    mixed_decode_chunk_forward: Optional[Callable[..., Any]] = None
    # Whether every matmul in the family's forwards goes through
    # models/quant.quantized_einsum (weight-only int8). MoE expert stacks
    # and the MLA latent path are not quant-aware yet.
    supports_int8: bool = False
    # Optional per-slot device state beside the KV pool (a recurrent state
    # per sequence): `slot_state(cfg, max_batch_size) -> {name: zeros
    # [layers, max_batch_size, ...]}`. The engine keeps the buffers in its
    # donated decode state under those names. With it, `prefill_forward`
    # returns a third value, the admitted sequence's final state
    # {name: [layers, 1, ...]}, which the engine writes over the slot's
    # (whole: that is also what clears the last occupant's), and
    # `decode_forward` takes `state=` and `live=` ([B] bool: the slots that
    # advance) and returns the buffers as a third value. Such a family has
    # no prefix to reuse and nothing to hand off: engine.py refuses what
    # it cannot run at start.
    slot_state: Optional[Callable[..., Any]] = None
    # With `slot_state`: `prefill_forward` also TAKES state (`state=`
    # {name: [layers, 1, ...]}: what the tokens start from) and the state
    # it returns is the state after them, so a prompt may be prefilled in
    # chunks that hand the slot's state on (engine.py `prefill_chunk`).
    prefill_carries_state: bool = False
    # Optional decode step of a family that routes tokens to experts:
    # `decode_forward`'s arguments and `live=` ([B] bool: the rows that
    # hold a running request; the others reach no expert), returning a
    # third value, int32 [2]: the rows this step routed, and the experts
    # that got at least one of them summed over the expert layers. The
    # engine brings the counts home in the result a decode call already
    # returns (`/stats`.engine_trace `moe_*`).
    decode_forward_routed: Optional[Callable[..., Any]] = None


_REGISTRY: dict[str, ModelFamily] = {}


def register_model_family(family: ModelFamily) -> None:
    _REGISTRY[family.name] = family


def get_model_family(name: str) -> ModelFamily:
    # Lazy imports so importing one family doesn't pull in all.
    if name not in _REGISTRY:
        if name in ("llama", "llama3"):
            from . import llama  # noqa: F401
        elif name in ("qwen2", "qwen2.5", "qwen"):
            from . import qwen2  # noqa: F401
        elif name in ("deepseek_moe", "deepseek"):
            from . import deepseek_moe  # noqa: F401
        elif name in ("qwen2_vl",):
            from . import qwen2_vl  # noqa: F401
        elif name == "gemma":
            from . import gemma  # noqa: F401
        elif name == "mixtral":
            from . import mixtral  # noqa: F401
        elif name == "granite_hybrid":
            from . import granite_hybrid  # noqa: F401
        elif name == "power_retention":
            from . import power_retention  # noqa: F401
    fam = _REGISTRY.get(name)
    if fam is None:
        raise ValueError(f"unknown model family: {name}")
    return fam


# ---- tiny/test/bench configs ------------------------------------------------
def tiny_config(**kw) -> ModelConfig:
    """CPU-test scale."""
    defaults = dict(vocab_size=512, hidden_size=128, num_layers=2,
                    num_heads=4, num_kv_heads=2, head_dim=32, ffn_size=256,
                    max_context_len=512)
    defaults.update(kw)
    return ModelConfig(**defaults)


def llama3_8b_config() -> ModelConfig:
    return ModelConfig(name="llama", vocab_size=128256, hidden_size=4096,
                       num_layers=32, num_heads=32, num_kv_heads=8,
                       head_dim=128, ffn_size=14336, rope_theta=500000.0,
                       max_context_len=8192)


def llama3_8b_l20_config() -> ModelConfig:
    """Llama-3-8B at every published width, cut to 20 of its 32 layers:
    the deepest bf16 cut one 16 GB v5e chip holds with the margin the
    full-depth int8 model has (decode program 13.1 GiB of 15.75 by the
    described-chip compile; 24 layers leave 1.1 GiB), so the same model
    can be served at --tp 1 and --tp 4 and compared (chip_smoke.py
    --chips 4). Depth is the only cut."""
    import dataclasses

    return dataclasses.replace(llama3_8b_config(), num_layers=20)


def llama3_70b_config() -> ModelConfig:
    return ModelConfig(name="llama", vocab_size=128256, hidden_size=8192,
                       num_layers=80, num_heads=64, num_kv_heads=8,
                       head_dim=128, ffn_size=28672, rope_theta=500000.0,
                       max_context_len=8192)


def bench_1b_config() -> ModelConfig:
    """~1.2B params — fits one v5e chip in bf16 with KV pool; the agent
    CLI's default `--model-config`."""
    return ModelConfig(name="llama", vocab_size=32768, hidden_size=2048,
                       num_layers=16, num_heads=16, num_kv_heads=8,
                       head_dim=128, ffn_size=8192, max_context_len=4096)
