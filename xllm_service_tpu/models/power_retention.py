"""Power-retention family (HF `brumby`: Brumby-14B-Base): a Qwen3-shaped
decoder whose mixer is power retention of degree 2 (`ops/retention.py`) in
every layer. No layer holds keys: the KV pool has no plane
(`cfg.kv_layers` 0) and neither attention kernel is called.

Per layer, with x the residual stream:

    h = RMSNorm(x);  q = RMSNorm_head(W_q h);  k = RMSNorm_head(W_k h);
    v = W_v h;       q, k <- rotary(q, k; half-split)
    log g = log sigmoid(W_g h + b_g)     one gate a KV head, float32
    o = retention(q, k, v, log g)        (`RETENTION_EPS` its normaliser's)
    x <- x + W_o o;   x <- x + SwiGLU(RMSNorm(x))

The config.json has Qwen3's keys only; the degree, the gate, the normaliser
and the state's type are this family's reading of the published mechanism,
listed under `assumed` in the benchmark's configuration.

Per sequence a layer carries ``S: [Hk, M, d, d]`` and ``z: [Hk, Mz, d]``,
float32: the family's `slot_state`, kept by the engine beside the (empty)
pool as `ret_s: [L, B, Hk, M, d, d]` and `ret_z: [L, B, Hk, Mz, d]`.

Prefill runs the chunked form from the state it is GIVEN (`state=`: the
slot's, or zeros for a sequence's first tokens) and returns the state after
its last valid token: the engine's prefill chunks hand it on from one to
the next (`ModelFamily.prefill_carries_state`). A bucket's padding must
not touch the state: k and log g are zeroed at and past `seq_len`. Decode
is the recurrence, one token, over the live slots only.

Shared with the dense families: `rms_norm`, `apply_rope`, and llama's
`_embed`, `_mlp` and `_unembed`. Layers are stacked and walked in an
unrolled loop with static indices, over the donated state buffers. No
`verify_forward`, no `mixed_decode_chunk_forward`, no int8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.attention import apply_rope, rms_norm
from ..ops.retention import (
    RETENTION_EPS,
    empty_state,
    retention_prefill,
    retention_update,
)
from ..parallel.sharding import ShardingRules
from .base import ModelConfig, ModelFamily, block, register_model_family
from .llama import _embed, _mlp, _unembed

Params = dict


def toy_config(**kw) -> ModelConfig:
    """CPU-test scale: 2 layers, 4 query and 2 KV heads of 16."""
    defaults = dict(
        name="power_retention", vocab_size=512, hidden_size=64, num_layers=2,
        num_heads=4, num_kv_heads=2, head_dim=16, ffn_size=128,
        layer_types=("retention",) * 2, rms_eps=1e-6, rope_theta=1e6,
        max_context_len=512)
    defaults.update(kw)
    return ModelConfig(**defaults)


def slot_state(cfg: ModelConfig, max_batch_size: int) -> dict:
    """The per-slot buffers, zeroed: float32 whatever the model's dtype."""
    s, z = empty_state(cfg.num_kv_heads, cfg.head_dim,
                       (cfg.num_layers, max_batch_size))
    return {"ret_s": s, "ret_z": z}


def init_params(cfg: ModelConfig, rng: jax.Array) -> Params:
    """Random init; leaves in the model's dtype but the gate's bias
    (float32), drawn so that the gate lies in about 0.9 to 0.999."""
    D, L, F = cfg.hidden_size, cfg.num_layers, cfg.ffn_size
    Hk, hd = cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(rng, 16))

    def dense(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (scale * fan_in ** -0.5)).astype(cfg.dtype)

    def ones(shape):
        return jnp.ones(shape, cfg.dtype)

    return {
        "embed": {"embedding": dense((cfg.vocab_size, D), D)},
        "layers": {
            "input_norm": {"scale": ones((L, D))},
            "q_proj": {"kernel": dense((L, D, cfg.q_size), D)},
            "k_proj": {"kernel": dense((L, D, cfg.kv_size), D)},
            "v_proj": {"kernel": dense((L, D, cfg.kv_size), D)},
            "q_norm": {"scale": ones((L, hd))},
            "k_norm": {"scale": ones((L, hd))},
            "g_proj": {"kernel": dense((L, D, Hk), D, 0.1),
                       "bias": jax.random.uniform(
                           next(keys), (L, Hk), jnp.float32, 2.2, 6.9)},
            "o_proj": {"kernel": dense((L, cfg.q_size, D), cfg.q_size)},
            "post_attn_norm": {"scale": ones((L, D))},
            "gate_proj": {"kernel": dense((L, D, F), D)},
            "up_proj": {"kernel": dense((L, D, F), D)},
            "down_proj": {"kernel": dense((L, F, D), F)},
        },
        "final_norm": {"scale": ones((D,))},
        "lm_head": {"kernel": dense((D, cfg.vocab_size), D)},
    }


# ------------------------------------------------------------------- parts
def _at(tree: Params, i: int) -> Params:
    return jax.tree.map(lambda a: a[i], tree)


def _qkvg(lp: Params, h: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """h [..., D] -> q [..., Hq, hd], k, v [..., Hk, hd] (q and k normed a
    head and rotated) and log g [..., Hk] float32."""
    def heads(name, n):
        y = jnp.einsum("...d,df->...f", h, lp[name]["kernel"])
        return y.reshape(*y.shape[:-1], n, cfg.head_dim)

    q = rms_norm(heads("q_proj", cfg.num_heads), lp["q_norm"]["scale"],
                 cfg.rms_eps)
    k = rms_norm(heads("k_proj", cfg.num_kv_heads), lp["k_norm"]["scale"],
                 cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    gate = jnp.einsum("...d,df->...f", h, lp["g_proj"]["kernel"])
    log_g = jax.nn.log_sigmoid(gate.astype(jnp.float32)
                               + lp["g_proj"]["bias"])
    return q, k, heads("v_proj", cfg.num_kv_heads), log_g


def _mixer_out(lp: Params, o: jax.Array, cfg: ModelConfig) -> jax.Array:
    o = o.astype(cfg.dtype).reshape(*o.shape[:-2], cfg.q_size)
    return jnp.einsum("...f,fd->...d", o, lp["o_proj"]["kernel"])


def _mlp_residual(lp: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with block("mlp"):
        return x + _mlp(lp, rms_norm(x, lp["post_attn_norm"]["scale"],
                                     cfg.rms_eps), cfg)


# ---------------------------------------------------------------- forwards
def prefill_forward(params: Params, cfg: ModelConfig,
                    tokens: jax.Array,        # [B, S] token ids
                    positions: jax.Array,     # [B, S] absolute positions
                    kv_pages: jax.Array,      # [0, ...]: no plane, untouched
                    page_table: jax.Array,    # [B, max_pages] unused
                    prefix_lens: jax.Array,   # [B] tokens already in `state`
                    seq_lens: jax.Array,      # [B] valid lengths
                    *, state: dict | None = None,
                    ) -> tuple[jax.Array, jax.Array, dict]:
    """Returns (last-token logits [B, V], kv_pages, the sequences' state
    after their last valid token {"ret_s": [L, B, Hk, M, d, d], "ret_z":
    [L, B, Hk, Mz, d]}). `state` is the state the tokens start from, in
    that shape (the chunk before them left it); None starts from empty."""
    del page_table, prefix_lens
    B, S = tokens.shape
    if state is None:
        state = slot_state(cfg, B)
    valid = jnp.arange(S)[None, :] < seq_lens[:, None]
    ret_s, ret_z = [], []
    x = _embed(params, cfg, tokens)
    for layer in range(cfg.num_layers):
        lp = _at(params["layers"], layer)
        with block("ret"):
            h = rms_norm(x, lp["input_norm"]["scale"], cfg.rms_eps)
            q, k, v, log_g = _qkvg(lp, h, cfg, positions)
            k = jnp.where(valid[..., None, None], k, 0)
            log_g = jnp.where(valid[..., None], log_g, 0.0)
            with jax.named_scope("ret.prefill"):
                outs = [retention_prefill(
                    q[b], k[b], v[b], log_g[b], state["ret_s"][layer, b],
                    state["ret_z"][layer, b], RETENTION_EPS)
                    for b in range(B)]
            o, s, z = (jnp.stack(part) for part in zip(*outs))
            ret_s.append(s)
            ret_z.append(z)
            x = x + _mixer_out(lp, o, cfg)
        x = _mlp_residual(lp, x, cfg)
    with block("head"):
        last = x[jnp.arange(B), jnp.maximum(seq_lens - 1, 0)]
    return (_unembed(params, cfg, last), kv_pages,
            {"ret_s": jnp.stack(ret_s), "ret_z": jnp.stack(ret_z)})


def decode_forward(params: Params, cfg: ModelConfig,
                   tokens: jax.Array,         # [B] last sampled tokens
                   positions: jax.Array,      # [B] their positions
                   kv_pages: jax.Array,       # [0, ...]: no plane, untouched
                   page_table: jax.Array,     # [B, max_pages] unused
                   context_lens: jax.Array,   # [B] unused
                   *, state: dict, live: jax.Array,
                   ) -> tuple[jax.Array, jax.Array, dict]:
    """One decode step over the engine's per-slot buffers `state`
    (`slot_state`'s, donated through the program). Returns (logits [B, V],
    kv_pages, state). Slots that are not `live` keep their state: it is
    neither read nor written for them."""
    del page_table, context_lens
    ret_s, ret_z = state["ret_s"], state["ret_z"]
    x = _embed(params, cfg, tokens)
    for layer in range(cfg.num_layers):
        lp = _at(params["layers"], layer)
        with block("ret"):
            h = rms_norm(x, lp["input_norm"]["scale"], cfg.rms_eps)
            q, k, v, log_g = _qkvg(lp, h, cfg, positions)
            with jax.named_scope("ret.update"):
                o, ret_s, ret_z = retention_update(
                    ret_s, ret_z, layer, live, q, k, v, log_g,
                    RETENTION_EPS)
            x = x + _mixer_out(lp, o, cfg)
        x = _mlp_residual(lp, x, cfg)
    return (_unembed(params, cfg, x), kv_pages,
            {"ret_s": ret_s, "ret_z": ret_z})


register_model_family(ModelFamily(
    name="power_retention",
    init_params=init_params,
    prefill_forward=prefill_forward,
    decode_forward=decode_forward,
    sharding_rules=ShardingRules(rules=[]),
    slot_state=slot_state,
    prefill_carries_state=True,
))
