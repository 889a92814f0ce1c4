"""Granite-4.0-H family (HF `granitemoehybrid`, dense): Mamba-2 mixers with
a few GQA attention layers among them, a SwiGLU MLP after every mixer.

Per layer l, kind from `cfg.layer_types`:

    h = h + r * mixer_l(RMSNorm(h));   h = h + r * W_out(silu(g) * u),
    [g | u] = W_in RMSNorm(h)

with r = `residual_multiplier`; embeddings times `embed_multiplier`, logits
(tied head) divided by `logits_scaling`.

*Attention*: q/k/v/o without bias, causal, softmax scale
`attn_multiplier`, and NO rotary embedding of any kind
(`position_embedding_type` "nope"; hf_config refuses another). Departure in storage, none in
mathematics: q, k and v are zero-padded from `head_dim` to
`cfg.kv_head_dim` (the lane width, 128) before `write_kv` /
`paged_attention(scale=...)` and the output is sliced back, so that decode
stays on the Pallas paged-attention kernel (its tiling needs a lane-width
head); the scores and the kept part of the output are unchanged. The pool
has one plane per ATTENTION layer (`cfg.kv_layers`), in layer order.

*Mamba-2* (one group): [z | xBC | dt] = W_in x (widths K | K + 2N | H with
K = H x P; held as two kernels, `in_proj` [z | xBC] and `dt_proj`: the
published K + K + 2N + H columns, 8512, are no lane-width multiple, for
which the TPU holds the matrix transposed and every call copied it back); xBC' = silu(causal depthwise conv of width W with bias);
split into x [H, P], B [N], C [N]; dt = softplus(dt + dt_bias),
A = -exp(A_log); S_t = exp(dt A) S_{t-1} + dt x (outer) B;
y = S_t C + D x; out = W_out RMSNorm(y * silu(z); w) over all K.
Per sequence a layer carries S (float32: it accumulates over every token)
and the last W-1 pre-activation xBC rows: the family's `slot_state`, kept
by the engine beside the KV pool as `ssm: [Lm, B, N, K]` (state-major: see
ops/pallas_ssm_update.py) and `conv: [Lm, B, W-1, K + 2N]`.

Prefill runs the scan chunked (`ops/ssm.ssm_chunked_scan`), from an empty
state (this family reuses no prefix); a bucket's padding must not touch the
state: dt is zeroed at and past `seq_len` and the convolution window is the
last W-1 VALID rows. Decode is the recurrence, one token, through
`ops/ssm.ssm_update` over the live slots only.

Layers of a kind are stacked (`mamba`, `attn`, `mlp`) and the forwards
walk them in an unrolled Python loop with static indices, as every family
here does, over the donated pool and state buffers. (A `lax.scan` over the
periods of the layer pattern compiles a quarter of the layers, but the TPU
compiler then copies each period's weight stacks out of the stacked leaves
and each layer's kernels out of those: 1.47 GiB of temporaries and the
weights streamed three times a decode step, by the described-chip compile's
HLO. PERF.md section 6, PR 34.) No `verify_forward`, no
`mixed_decode_chunk_forward`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.attention import (
    paged_attention,
    prefill_attention,
    rms_norm,
    write_kv,
)
from ..ops.ssm import causal_conv, conv_step, ssm_chunked_scan, ssm_update
from ..parallel.sharding import ShardingRules
from .base import ModelConfig, ModelFamily, block, register_model_family

Params = dict


def toy_config(**kw) -> ModelConfig:
    """CPU-test scale: two periods of a five-layer pattern with one
    attention layer, eight state-space heads of 16, state 32."""
    defaults = dict(
        name="granite_hybrid", vocab_size=512, hidden_size=64, num_layers=10,
        num_heads=4, num_kv_heads=2, head_dim=16, ffn_size=128,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba") * 2,
        ssm_heads=8, ssm_head_dim=16, ssm_state=32, ssm_conv=4, ssm_chunk=8,
        embed_multiplier=12.0, residual_multiplier=0.22,
        attn_multiplier=1.0 / 16, logits_scaling=8.0, kv_held_dim=128,
        tie_embeddings=True, rope_theta=10000.0, max_context_len=512)
    defaults.update(kw)
    return ModelConfig(**defaults)


# ------------------------------------------------------------------ shapes
def _layers(cfg: ModelConfig):
    """(layer, kind, its index among the layers of its kind), in order."""
    types = cfg.layer_types
    if len(types) != cfg.num_layers or set(types) - {"mamba", "attention"}:
        raise ValueError(f"layer_types {types!r} must name mamba or "
                         f"attention for each of {cfg.num_layers} layers")
    seen = {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(types):
        yield layer, kind, seen[kind]
        seen[kind] += 1


def _widths(cfg: ModelConfig) -> tuple[int, int, int, int]:
    """(H, P, N, K + 2N): heads, head dim, state size, convolved width."""
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    return H, P, N, H * P + 2 * N


def slot_state(cfg: ModelConfig, max_batch_size: int) -> dict:
    """The per-slot buffers, zeroed: `ssm` float32 whatever the model's
    dtype, `conv` in the model's."""
    H, P, N, C = _widths(cfg)
    Lm = cfg.num_layers - cfg.kv_layers
    return {
        "ssm": jnp.zeros((Lm, max_batch_size, N, H * P), jnp.float32),
        "conv": jnp.zeros((Lm, max_batch_size, cfg.ssm_conv - 1, C),
                          cfg.dtype),
    }


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    """Random init; leaves in the model's dtype but the recurrence's own
    (dt_bias, A_log, D: float32)."""
    D, L, F = cfg.hidden_size, cfg.num_layers, cfg.ffn_size
    La = cfg.kv_layers
    Lm = L - La
    H, P, N, C = _widths(cfg)
    K = H * P
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    def ones(shape):
        return jnp.ones(shape, cfg.dtype)

    return {
        "embed": {"embedding": dense((cfg.vocab_size, D), D)},
        "mamba": {
            "norm": {"scale": ones((Lm, D))},
            "in_proj": {"kernel": dense((Lm, D, K + C), D)},
            "dt_proj": {"kernel": dense((Lm, D, H), D)},
            "conv": {"kernel": dense((Lm, cfg.ssm_conv, C), cfg.ssm_conv),
                     "bias": jnp.zeros((Lm, C), cfg.dtype)},
            "dt_bias": jax.random.uniform(next(keys), (Lm, H), jnp.float32,
                                          -4.0, -1.0),
            "A_log": jnp.log(jax.random.uniform(next(keys), (Lm, H),
                                                jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((Lm, H), jnp.float32),
            "gate_norm": {"scale": ones((Lm, K))},
            "out_proj": {"kernel": dense((Lm, K, D), K)},
        },
        "attn": {
            "norm": {"scale": ones((La, D))},
            "q_proj": {"kernel": dense((La, D, cfg.q_size), D)},
            "k_proj": {"kernel": dense((La, D, cfg.kv_size), D)},
            "v_proj": {"kernel": dense((La, D, cfg.kv_size), D)},
            "o_proj": {"kernel": dense((La, cfg.q_size, D), cfg.q_size)},
        },
        "mlp": {
            "norm": {"scale": ones((L, D))},
            "in_proj": {"kernel": dense((L, D, 2 * F), D)},
            "out_proj": {"kernel": dense((L, F, D), F)},
        },
        "final_norm": {"scale": ones((D,))},
    }


# ------------------------------------------------------------------- parts
def _mlp(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
    gu = jnp.einsum("...d,df->...f", h, lp["in_proj"]["kernel"])
    g, u = jnp.split(gu, 2, axis=-1)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u,
                      lp["out_proj"]["kernel"])


def _qkv(lp: Params, h: jax.Array, cfg: ModelConfig):
    """h [..., D] -> q [..., n_q, hd], k, v [..., n_kv, hd] at the model's
    own head size; no position enters."""
    def heads(name, n):
        y = jnp.einsum("...d,df->...f", h, lp[name]["kernel"])
        return y.reshape(*y.shape[:-1], n, cfg.head_dim)

    return (heads("q_proj", cfg.num_heads), heads("k_proj", cfg.num_kv_heads),
            heads("v_proj", cfg.num_kv_heads))


def _lanes(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[..., head_dim] -> [..., kv_head_dim], zeros behind."""
    pad = cfg.kv_head_dim - cfg.head_dim
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


def _attn_scale(cfg: ModelConfig) -> float:
    return cfg.attn_multiplier or cfg.head_dim ** -0.5


def _attn_out(lp: Params, a: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Attention output [..., n_q, >= head_dim] (the kernel's comes at the
    lane width: the zeros behind are sliced off) through o_proj."""
    a = a[..., :cfg.head_dim]
    return jnp.einsum("...f,fd->...d", a.reshape(*a.shape[:-2], cfg.q_size),
                      lp["o_proj"]["kernel"])


def _split_in_proj(lp: Params, h: jax.Array, cfg: ModelConfig):
    H, P, _, _ = _widths(cfg)
    zx = jnp.einsum("...d,df->...f", h, lp["in_proj"]["kernel"])
    dt = jnp.einsum("...d,df->...f", h, lp["dt_proj"]["kernel"])
    return zx[..., :H * P], zx[..., H * P:], dt            # z, xBC, dt


def _split_conv(xbc: jax.Array, cfg: ModelConfig):
    """silu(conv output) [..., K + 2N] f32 -> x [..., H, P], B, C [..., N],
    rounded to the model's dtype as every activation is."""
    H, P, N, _ = _widths(cfg)
    xbc = jax.nn.silu(xbc).astype(cfg.dtype)
    x, b, c = jnp.split(xbc, [H * P, H * P + N], axis=-1)
    return x.reshape(*x.shape[:-1], H, P), b, c


def _dt(lp: Params, dt: jax.Array) -> jax.Array:
    return jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])


def _mamba_out(lp: Params, y: jax.Array, x: jax.Array, z: jax.Array,
               cfg: ModelConfig) -> jax.Array:
    """y [..., H, P] f32 from the scan; adds the skip, gates, norms over
    all K (one group) and projects out."""
    y = y + lp["D"][:, None] * x.astype(jnp.float32)
    y = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y, lp["gate_norm"]["scale"], cfg.rms_eps).astype(cfg.dtype)
    return jnp.einsum("...k,kd->...d", y, lp["out_proj"]["kernel"])


def _at(tree: Params, i: int) -> Params:
    return jax.tree.map(lambda a: a[i], tree)


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed"]["embedding"][tokens].astype(cfg.dtype)
    return x * jnp.asarray(cfg.embed_multiplier, cfg.dtype)


@block("head")
def _unembed(params: Params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"]["scale"], cfg.rms_eps)
    logits = jnp.einsum("...d,vd->...v", x, params["embed"]["embedding"])
    return logits.astype(jnp.float32) / cfg.logits_scaling


# ---------------------------------------------------------------- forwards
def prefill_forward(params: Params, cfg: ModelConfig,
                    tokens: jax.Array,        # [B, S] token ids
                    positions: jax.Array,     # [B, S] unused: no position embedding
                    kv_pages: jax.Array,      # [La, 2, P, n_kv, ps, 128]
                    page_table: jax.Array,    # [B, max_pages]
                    prefix_lens: jax.Array,   # [B]: zeros (no prefix reuse)
                    seq_lens: jax.Array,      # [B] valid lengths
                    ) -> tuple[jax.Array, jax.Array, dict]:
    """Returns (last-token logits [B, V], kv_pages, the sequences' final
    state {"ssm": [Lm, B, N, K], "conv": [Lm, B, W-1, K+2N]}). Starts
    every sequence from an empty state, whatever `prefix_lens` says: the
    engine asks the prefix cache nothing for this family."""
    r = jnp.asarray(cfg.residual_multiplier, cfg.dtype)
    valid = jnp.arange(tokens.shape[1])[None, :] < seq_lens[:, None]
    zero = jnp.zeros_like(seq_lens)
    ssm, conv = [], []
    x = _embed(params, cfg, tokens)
    for layer, kind, i in _layers(cfg):
        with block("ssm" if kind == "mamba" else "attn"):
            if kind == "mamba":
                lp = _at(params["mamba"], i)
                h = rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
                z, xbc, dt = _split_in_proj(lp, h, cfg)
                with jax.named_scope("ssm_conv"):
                    out, window = causal_conv(xbc, lp["conv"]["kernel"],
                                              lp["conv"]["bias"], seq_lens)
                    xs, b, c = _split_conv(out, cfg)
                with jax.named_scope("ssm_scan"):
                    dtv = jnp.where(valid[..., None], _dt(lp, dt), 0.0)
                    y, s = ssm_chunked_scan(xs, dtv, -jnp.exp(lp["A_log"]),
                                            b, c, cfg.ssm_chunk)
                mix = _mamba_out(lp, y, xs, z, cfg)
                ssm.append(s)
                conv.append(window.astype(cfg.dtype))
            else:
                lp = _at(params["attn"], i)
                h = rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
                q, k, v = _qkv(lp, h, cfg)
                k, v = _lanes(k, cfg), _lanes(v, cfg)
                kv_pages = write_kv(kv_pages, i, k, v, page_table, zero,
                                    seq_lens)
                mix = _attn_out(lp, prefill_attention(
                    _lanes(q, cfg), k, v, kv_pages, i, page_table, zero,
                    seq_lens, scale=_attn_scale(cfg)), cfg)
            x = x + r * mix
        with block("mlp"):
            x = x + r * _mlp(_at(params["mlp"], layer), x, cfg)
    with block("head"):
        last = x[jnp.arange(x.shape[0]), jnp.maximum(seq_lens - 1, 0)]
    return (_unembed(params, cfg, last), kv_pages,
            {"ssm": jnp.stack(ssm), "conv": jnp.stack(conv)})


def decode_forward(params: Params, cfg: ModelConfig,
                   tokens: jax.Array,         # [B] last sampled tokens
                   positions: jax.Array,      # [B] unused: no position embedding
                   kv_pages: jax.Array,       # [La, 2, P, n_kv, ps, 128]
                   page_table: jax.Array,     # [B, max_pages]
                   context_lens: jax.Array,   # [B] lens INCLUDING new token
                   *, state: dict, live: jax.Array,
                   ) -> tuple[jax.Array, jax.Array, dict]:
    """One decode step over the engine's per-slot buffers `state`
    (`slot_state`'s, donated through the program). Returns (logits [B, V],
    kv_pages, state). Slots that are not `live` keep their state: the
    recurrent part is neither read nor written for them."""
    r = jnp.asarray(cfg.residual_multiplier, cfg.dtype)
    ssm, conv = state["ssm"], state["conv"]
    x = _embed(params, cfg, tokens)
    for layer, kind, i in _layers(cfg):
        with block("ssm" if kind == "mamba" else "attn"):
            if kind == "mamba":
                lp = _at(params["mamba"], i)
                h = rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
                z, xbc, dt = _split_in_proj(lp, h, cfg)
                with jax.named_scope("ssm_conv"):
                    out, window = conv_step(conv[i], xbc,
                                            lp["conv"]["kernel"],
                                            lp["conv"]["bias"])
                    conv = conv.at[i].set(
                        jnp.where(live[:, None, None], window, conv[i]))
                    xs, b, c = _split_conv(out, cfg)
                with jax.named_scope("ssm_update"):
                    y, ssm = ssm_update(ssm, i, live, xs, _dt(lp, dt),
                                        -jnp.exp(lp["A_log"]), b, c)
                mix = _mamba_out(lp, y, xs, z, cfg)
            else:
                lp = _at(params["attn"], i)
                h = rms_norm(x, lp["norm"]["scale"], cfg.rms_eps)
                q, k, v = _qkv(lp, h, cfg)
                kv_pages = write_kv(kv_pages, i, _lanes(k, cfg)[:, None],
                                    _lanes(v, cfg)[:, None], page_table,
                                    context_lens - 1,
                                    jnp.ones_like(context_lens))
                mix = _attn_out(lp, paged_attention(
                    _lanes(q, cfg), kv_pages, i, page_table, context_lens,
                    scale=_attn_scale(cfg)), cfg)
            x = x + r * mix
        with block("mlp"):
            x = x + r * _mlp(_at(params["mlp"], layer), x, cfg)
    return _unembed(params, cfg, x), kv_pages, {"ssm": ssm, "conv": conv}


register_model_family(ModelFamily(
    name="granite_hybrid",
    init_params=init_params,
    prefill_forward=prefill_forward,
    decode_forward=decode_forward,
    sharding_rules=ShardingRules(rules=[]),
    slot_state=slot_state,
))
