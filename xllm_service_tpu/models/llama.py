"""Llama-3 family (functional JAX, stacked layers, paged KV).

Covers BASELINE configs 1-2 (Llama-3-8B single-instance and PD-disagg) and
the 70B north star. Architecture: RMSNorm, GQA attention with RoPE, SwiGLU
MLP, optional tied embeddings. Layers are stacked with a leading L dim and
executed with `lax.scan` — a single compiled layer body.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..ops.attention import (
    apply_rope,
    decode_attention_step,
    prefill_attention,
    rms_norm,
    write_kv,
)
from ..parallel.sharding import ShardingRules
from jax.sharding import PartitionSpec as P
from ..parallel.mesh import AXIS_MODEL
from .base import ModelConfig, ModelFamily, block, register_model_family
from .quant import quantized_einsum

Params = dict


# Stacked-layer sharding rules (leading L dim on every layer tensor).
# int8-quant `/scale` leaves come FIRST (first match wins): a scale is
# [L, out] — sharded with the kernel's output dim for column-parallel
# weights, replicated for row-parallel ones (whose sharded dim is the
# contraction the scale reduced over). The `q8` leaf has the kernel's own
# shape and inherits its spec via the plain `/kernel` patterns.
LLAMA_STACKED_RULES = ShardingRules(rules=[
    (r"embed/embedding", P(AXIS_MODEL, None)),
    (r"(q_proj|k_proj|v_proj|gate_proj|up_proj)/kernel/scale",
     P(None, AXIS_MODEL)),
    (r"(o_proj|down_proj)/kernel/scale", P()),
    (r"lm_head/kernel/scale", P(AXIS_MODEL)),
    (r"(q_proj|k_proj|v_proj)/kernel", P(None, None, AXIS_MODEL)),
    (r"(q_proj|k_proj|v_proj)/bias", P(None, AXIS_MODEL)),
    (r"o_proj/kernel", P(None, AXIS_MODEL, None)),
    (r"(gate_proj|up_proj)/kernel", P(None, None, AXIS_MODEL)),
    (r"down_proj/kernel", P(None, AXIS_MODEL, None)),
    (r"lm_head/kernel", P(None, AXIS_MODEL)),
])


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    """Random init (truncated-normal-ish scaled); bf16 leaves."""
    keys = jax.random.split(rng, 8)
    D, L = cfg.hidden_size, cfg.num_layers
    Hq, Hkv, hd, F = cfg.q_size, cfg.kv_size, cfg.head_dim, cfg.ffn_size

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    def norm_init(shape):
        # gemma stores w with the norm computing (1 + w): zeros == identity.
        return (jnp.zeros if cfg.rms_unit_offset else jnp.ones)(
            shape, cfg.dtype)

    params: Params = {
        "embed": {"embedding": dense(keys[0], (cfg.vocab_size, D), D)},
        "layers": {
            "input_norm": {"scale": norm_init((L, D))},
            "q_proj": {"kernel": dense(keys[1], (L, D, Hq), D)},
            "k_proj": {"kernel": dense(keys[2], (L, D, Hkv), D)},
            "v_proj": {"kernel": dense(keys[3], (L, D, Hkv), D)},
            "o_proj": {"kernel": dense(keys[4], (L, Hq, D), Hq)},
            "post_attn_norm": {"scale": norm_init((L, D))},
            "gate_proj": {"kernel": dense(keys[5], (L, D, F), D)},
            "up_proj": {"kernel": dense(keys[6], (L, D, F), D)},
            "down_proj": {"kernel": dense(keys[7], (L, F, D), F)},
        },
        "final_norm": {"scale": norm_init((D,))},
    }
    if cfg.qkv_bias:
        params["layers"]["q_proj"]["bias"] = jnp.zeros((L, Hq), cfg.dtype)
        params["layers"]["k_proj"]["bias"] = jnp.zeros((L, Hkv), cfg.dtype)
        params["layers"]["v_proj"]["bias"] = jnp.zeros((L, Hkv), cfg.dtype)
    if cfg.sandwich_norms:   # gemma-2: pre/post feed-forward norms
        params["layers"]["pre_ffw_norm"] = {"scale": norm_init((L, D))}
        params["layers"]["post_ffw_norm"] = {"scale": norm_init((L, D))}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense(
            jax.random.fold_in(rng, 99), (D, cfg.vocab_size), D)}
    return params


def _project_qkv(lp: Params, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array):
    """x: [B, S, D] (or [B, D] for decode with S folded) -> q,k,v heads."""
    q = quantized_einsum("...d,df->...f", x, lp["q_proj"]["kernel"])
    k = quantized_einsum("...d,df->...f", x, lp["k_proj"]["kernel"])
    v = quantized_einsum("...d,df->...f", x, lp["v_proj"]["kernel"])
    if "bias" in lp["q_proj"]:
        q = q + lp["q_proj"]["bias"]
        k = k + lp["k_proj"]["bias"]
        v = v + lp["v_proj"]["bias"]
    q = q.reshape(*q.shape[:-1], cfg.num_heads, cfg.head_dim)
    k = k.reshape(*k.shape[:-1], cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(*v.shape[:-1], cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.mrope_section)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.mrope_section)
    return q, k, v


def _attn_opts(cfg: ModelConfig, layer: int) -> dict:
    """Per-layer attention kwargs for the gemma-2 extras: explicit query
    scale (query_pre_attn_scalar), score softcap, and the sliding window
    on local layers. Empty for every other family — keeping `scale=None`
    preserves the Pallas-kernel eligibility gates."""
    opts: dict = {}
    if cfg.query_pre_attn_scalar > 0:
        opts["scale"] = cfg.query_pre_attn_scalar ** -0.5
    if cfg.attn_logit_softcap > 0:
        opts["softcap"] = cfg.attn_logit_softcap
    if cfg.layer_is_local(layer):
        opts["window"] = cfg.sliding_window
    return opts


def _norm(x: jax.Array, scale: jax.Array, cfg: ModelConfig) -> jax.Array:
    """RMSNorm; the gemma family stores w with the norm computing
    (1 + w) (rms_unit_offset)."""
    if cfg.rms_unit_offset:
        scale = 1.0 + scale.astype(jnp.float32)
    return rms_norm(x, scale, cfg.rms_eps)


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    if cfg.embed_scale:   # gemma scales embeddings by sqrt(hidden)
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, cfg.dtype)
    return x


def _mlp(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    gate = quantized_einsum("...d,df->...f", x, lp["gate_proj"]["kernel"])
    up = quantized_einsum("...d,df->...f", x, lp["up_proj"]["kernel"])
    act = (jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu)(gate)
    return quantized_einsum("...f,fd->...d", act * up,
                            lp["down_proj"]["kernel"])


def _attn_mlp_residual(lp: Params, x: jax.Array, attn: jax.Array,
                       cfg: ModelConfig) -> jax.Array:
    """Fold the attention output and the MLP into the residual stream.
    sandwich_norms (gemma-2) norms the attention/MLP OUTPUTS as well:
    x += post_attn_norm(o_proj(attn)); x += post_ffw_norm(mlp(pre_ffw_norm(x)))."""
    with block("attn"):
        o = quantized_einsum("...f,fd->...d", attn, lp["o_proj"]["kernel"])
        if cfg.sandwich_norms:
            o = _norm(o, lp["post_attn_norm"]["scale"], cfg)
        x = x + o
    with block("mlp"):
        if cfg.sandwich_norms:
            h2 = _norm(x, lp["pre_ffw_norm"]["scale"], cfg)
            return x + _norm(_mlp(lp, h2, cfg),
                             lp["post_ffw_norm"]["scale"], cfg)
        h2 = _norm(x, lp["post_attn_norm"]["scale"], cfg)
        return x + _mlp(lp, h2, cfg)


@block("head")
def _unembed(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = _norm(x, params["final_norm"]["scale"], cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"]["embedding"])
    else:
        logits = quantized_einsum("...d,dv->...v", x,
                                  params["lm_head"]["kernel"])
    logits = logits.astype(jnp.float32)
    if cfg.final_logit_softcap > 0:   # gemma-2 style tanh capping
        cap = cfg.final_logit_softcap
        logits = cap * jnp.tanh(logits / cap)
    return logits


def prefill_forward(params: Params, cfg: ModelConfig,
                    tokens: jax.Array,        # [B, S] suffix token ids
                    positions: jax.Array,     # [B, S] absolute positions
                    kv_pages: jax.Array,      # [L, 2, P, n_kv, ps, hd]
                    page_table: jax.Array,    # [B, max_pages]
                    prefix_lens: jax.Array,   # [B] cached-prefix lengths
                    seq_lens: jax.Array,      # [B] valid suffix lengths
                    ) -> tuple[jax.Array, jax.Array]:
    """Returns (last-token logits [B, V], updated kv_pages)."""
    x = _embed(params, cfg, tokens)
    return prefill_from_embeddings(params, cfg, x, positions, kv_pages,
                                   page_table, prefix_lens, seq_lens)


def prefill_from_embeddings(params: Params, cfg: ModelConfig,
                            x: jax.Array, positions: jax.Array,
                            kv_pages: jax.Array, page_table: jax.Array,
                            prefix_lens: jax.Array, seq_lens: jax.Array,
                            all_logits: bool = False,
                            ) -> tuple[jax.Array, jax.Array]:
    """Prefill body over precomputed input embeddings (multimodal families
    splice visual tokens before calling this).

    Layers run as an unrolled Python loop over the ONE donated pool: each
    layer's K/V is written into it in place (`write_kv`) and read where it
    lies (`(pool, layer)`); no layer is sliced out or stacked back. (A
    `lax.scan` whose ys re-stack the pool copies the entire KV cache
    every call, and so did a per-layer slice + `dynamic_update_index_in_dim`:
    a third of a decode step on the chip, PERF.md §6 PR 25.)

    all_logits=True returns logits for EVERY position [B, S, V] (the
    speculative-decoding verify path needs per-position predictions);
    default returns only the last valid token's [B, V].
    """

    for l in range(cfg.num_layers):
        with block("attn"):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = _norm(x, lp["input_norm"]["scale"], cfg)
            q, k, v = _project_qkv(lp, h, cfg, positions)
            kv_pages = write_kv(kv_pages, l, k, v, page_table, prefix_lens,
                                seq_lens)
            attn = prefill_attention(q, k, v, kv_pages, l,
                                     page_table, prefix_lens, seq_lens,
                                     **_attn_opts(cfg, l))
            attn = attn.reshape(*attn.shape[:-2], cfg.q_size)
        x = _attn_mlp_residual(lp, x, attn, cfg)
    if all_logits:
        return _unembed(params, cfg, x), kv_pages
    # Last valid token's hidden state per row.
    with block("head"):
        idx = jnp.maximum(seq_lens - 1, 0)
        last = x[jnp.arange(x.shape[0]), idx]
    return _unembed(params, cfg, last), kv_pages


def embed_forward(params: Params, cfg: ModelConfig,
                  tokens: jax.Array,      # [B, S] padded token ids
                  seq_lens: jax.Array,    # [B] valid lengths
                  ) -> jax.Array:
    """Text embeddings: dense causal forward (no paged cache), final norm,
    mean-pool over valid positions -> [B, D] f32. Powers /v1/embeddings —
    which the reference stubs as "not support"
    (`http_service/service.cpp:500-517`)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :],
                                 (B, S))
    x = _embed(params, cfg, tokens)

    def layer_body(l, x):
        with block("attn"):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = _norm(x, lp["input_norm"]["scale"], cfg)
            q, k, v = _project_qkv(lp, h, cfg, positions)
            attn = prefill_attention(q, k, v, None, None, None,
                                     jnp.zeros((B,), jnp.int32), seq_lens,
                                     **_attn_opts(cfg, l))
            attn = attn.reshape(*attn.shape[:-2], cfg.q_size)
        return _attn_mlp_residual(lp, x, attn, cfg)

    for l in range(cfg.num_layers):
        x = layer_body(l, x)
    with block("head"):
        x = _norm(x, params["final_norm"]["scale"], cfg)
        mask = (jnp.arange(S)[None, :] < seq_lens[:, None])[..., None]
        summed = jnp.sum(jnp.where(mask, x.astype(jnp.float32), 0.0),
                         axis=1)
        return summed / jnp.maximum(seq_lens[:, None], 1)


def verify_forward(params: Params, cfg: ModelConfig,
                   tokens: jax.Array,        # [B, S] block to verify
                   positions: jax.Array,     # [B, S]
                   kv_pages: jax.Array, page_table: jax.Array,
                   prefix_lens: jax.Array,   # [B] KV already in cache
                   seq_lens: jax.Array,      # [B] valid block lengths
                   ) -> tuple[jax.Array, jax.Array]:
    """Speculative-decoding verify: one forward over a short multi-token
    block per sequence (last accepted token + draft tokens), returning
    logits at EVERY block position [B, S, V] + updated KV. Structurally a
    batched mini-prefill against the paged cache."""
    x = _embed(params, cfg, tokens)
    return prefill_from_embeddings(params, cfg, x, positions, kv_pages,
                                   page_table, prefix_lens, seq_lens,
                                   all_logits=True)


def decode_forward(params: Params, cfg: ModelConfig,
                   tokens: jax.Array,         # [B] last sampled tokens
                   positions: jax.Array,      # [B] their absolute positions
                   kv_pages: jax.Array,       # [L, 2, P, n_kv, ps, hd]
                   page_table: jax.Array,     # [B, max_pages]
                   context_lens: jax.Array,   # [B] lens INCLUDING new token
                   rope_positions: jax.Array | None = None,
                   ) -> tuple[jax.Array, jax.Array]:
    """One decode step. Returns (logits [B, V], updated kv_pages).

    Unrolled layer loop over the one donated pool (see
    prefill_from_embeddings): the token's K/V is appended in place and the
    kernel reads `(pool, layer)`."""
    x = _embed(params, cfg, tokens)                            # [B, D]
    # M-RoPE (qwen2_vl): rope rotates by the multimodal position id
    # (sequence index + per-slot delta after image grids), while KV
    # writes/paging stay on the plain sequence index.
    if rope_positions is None:
        rope_positions = positions

    for l in range(cfg.num_layers):
        with block("attn"):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = _norm(x, lp["input_norm"]["scale"], cfg)
            q, k, v = _project_qkv(lp, h, cfg, rope_positions)    # [B, H, hd]
            attn, kv_pages = decode_attention_step(
                q, k, v, kv_pages, l, page_table, context_lens,
                **_attn_opts(cfg, l))
            attn = attn.reshape(*attn.shape[:-2], cfg.q_size)
        x = _attn_mlp_residual(lp, x, attn, cfg)
    return _unembed(params, cfg, x), kv_pages


def mixed_decode_chunk_forward(
        params: Params, cfg: ModelConfig,
        dec_tokens: jax.Array,      # [B] last sampled tokens
        dec_positions: jax.Array,   # [B] their absolute positions
        chunk_tokens: jax.Array,    # [c] prefill sub-chunk (one sequence)
        chunk_positions: jax.Array,  # [c] absolute positions in its prompt
        kv_pages: jax.Array,        # [L, 2, P, n_kv, ps, hd]
        dec_pt: jax.Array,          # [B, max_pages]
        chunk_pt: jax.Array,        # [1, max_pages] the chunk seq's table
        dec_clens: jax.Array,       # [B] incl. the new token
        chunk_start: jax.Array,     # [] tokens of the prompt written so far
        chunk_valid: jax.Array,     # [] live tokens in this sub-chunk (<=c)
) -> tuple[jax.Array, jax.Array]:
    """Sarathi-style mixed step (SURVEY §7.3 hard-part 2; the reference's
    continuous-batching north star, BASELINE.json): one forward that
    decodes the running batch AND writes+attends a sub-chunk of one
    prefilling sequence. Every projection / MLP / unembed GEMM runs over
    the CONCATENATED token rows, so at serving batch sizes the decode
    rows ride the prefill chunk's weight stream instead of paying their
    own HBM pass — and decode never pauses while a long prompt installs.

    Returns (decode-row logits [B, V], updated kv_pages). The chunk rows'
    logits are discarded (mid-prompt positions; the FINAL chunk samples
    the first token through the normal install program). Padding rows
    (chunk_valid < c) write to the garbage page and attend nothing.
    """
    B = dec_tokens.shape[0]
    c = chunk_tokens.shape[0]
    x = jnp.concatenate([_embed(params, cfg, dec_tokens),
                         _embed(params, cfg, chunk_tokens)])   # [B+c, D]
    rope_pos = jnp.concatenate([dec_positions, chunk_positions])
    chunk_prefix = chunk_start[None]                           # [1]
    chunk_lens = chunk_valid[None]                             # [1]

    for l in range(cfg.num_layers):
        with block("attn"):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = _norm(x, lp["input_norm"]["scale"], cfg)
            q, k, v = _project_qkv(lp, h, cfg, rope_pos)      # [B+c, H, hd]
            # Chunk KV lands in the pool FIRST (its own pages; decode rows
            # belong to different sequences, so order is immaterial there).
            kv_pages = write_kv(kv_pages, l, k[None, B:], v[None, B:],
                                chunk_pt, chunk_prefix, chunk_lens)
            attn_d, kv_pages = decode_attention_step(
                q[:B], k[:B], v[:B], kv_pages, l, dec_pt, dec_clens,
                **_attn_opts(cfg, l))
            attn_c = prefill_attention(
                q[None, B:], k[None, B:], v[None, B:], kv_pages, l,
                chunk_pt, chunk_prefix, chunk_lens, **_attn_opts(cfg, l))
            attn = jnp.concatenate([attn_d, attn_c[0]])
            attn = attn.reshape(B + c, cfg.q_size)
        x = _attn_mlp_residual(lp, x, attn, cfg)
    return _unembed(params, cfg, x[:B]), kv_pages


register_model_family(ModelFamily(
    name="llama",
    init_params=init_params,
    prefill_forward=prefill_forward,
    decode_forward=decode_forward,
    sharding_rules=LLAMA_STACKED_RULES,
    verify_forward=verify_forward,
    embed_forward=embed_forward,
    mixed_decode_chunk_forward=mixed_decode_chunk_forward,
    supports_int8=True,
))
