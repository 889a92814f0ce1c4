"""Batched token sampling (jit-compiled with the decode step).

Per-slot controls arrive as device arrays so one compiled program serves any
mix of greedy/temperature/top-k/top-p/penalty settings — no recompiles when
request parameters vary (XLA static-shape discipline).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


# Per-slot sparse logit_bias capacity (OpenAI caps the map at 300 keys;
# 32 covers practical use — extra keys are dropped oldest-last).
NUM_BIAS = 32


@dataclass
class SamplingState:
    """Device-side per-slot sampling controls + penalty bookkeeping."""

    temperature: jax.Array        # [B] f32; 0 => greedy
    top_k: jax.Array              # [B] i32; <=0 => disabled
    top_p: jax.Array              # [B] f32; >=1 => disabled
    frequency_penalty: jax.Array  # [B] f32
    presence_penalty: jax.Array   # [B] f32
    repetition_penalty: jax.Array  # [B] f32; 1 => disabled
    token_counts: jax.Array       # [B, V] i32 — occurrences in prompt+output
    bias_ids: jax.Array = None    # [B, NUM_BIAS] i32; -1 = empty
    bias_vals: jax.Array = None   # [B, NUM_BIAS] f32

    @classmethod
    def init(cls, batch: int, vocab: int) -> "SamplingState":
        return cls(
            temperature=jnp.ones((batch,), jnp.float32),
            top_k=jnp.zeros((batch,), jnp.int32),
            top_p=jnp.ones((batch,), jnp.float32),
            frequency_penalty=jnp.zeros((batch,), jnp.float32),
            presence_penalty=jnp.zeros((batch,), jnp.float32),
            repetition_penalty=jnp.ones((batch,), jnp.float32),
            token_counts=jnp.zeros((batch, vocab), jnp.int32),
            bias_ids=jnp.full((batch, NUM_BIAS), -1, jnp.int32),
            bias_vals=jnp.zeros((batch, NUM_BIAS), jnp.float32),
        )


def apply_penalties(logits: jax.Array, st: SamplingState) -> jax.Array:
    """OpenAI-style logit_bias + frequency/presence + HF-style repetition
    penalties."""
    if st.bias_ids is not None:
        B = logits.shape[0]
        rows = jnp.arange(B)[:, None]
        safe = jnp.where(st.bias_ids >= 0, st.bias_ids, 0)
        vals = jnp.where(st.bias_ids >= 0, st.bias_vals, 0.0)
        logits = logits.at[rows, safe].add(vals)
    counts = st.token_counts.astype(jnp.float32)
    seen = (counts > 0).astype(jnp.float32)
    logits = logits - counts * st.frequency_penalty[:, None]
    logits = logits - seen * st.presence_penalty[:, None]
    rep = st.repetition_penalty[:, None]
    penalized = jnp.where(logits > 0, logits / rep, logits * rep)
    logits = jnp.where(seen > 0, penalized, logits)
    return logits


def _mask_top_k(logits: jax.Array, top_k: jax.Array) -> jax.Array:
    """Per-row top-k mask with dynamic k (static-shape via sort threshold)."""
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k = jnp.clip(top_k, 1, V)
    thresh = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)
    keep = (logits >= thresh) | (top_k[:, None] <= 0)
    return jnp.where(keep, logits, _NEG_INF)


def _mask_top_p(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """Nucleus mask: keep the smallest set of tokens with cumprob >= p."""
    probs = jax.nn.softmax(logits, axis=-1)
    sorted_probs = jnp.sort(probs, axis=-1)[:, ::-1]
    cum = jnp.cumsum(sorted_probs, axis=-1)
    # Threshold prob: smallest sorted prob whose cumulative mass is still
    # below p keeps its place; everything smaller is dropped.
    still_needed = cum - sorted_probs < top_p[:, None]
    thresh = jnp.min(jnp.where(still_needed, sorted_probs, 2.0),
                     axis=-1, keepdims=True)
    keep = (probs >= thresh) | (top_p[:, None] >= 1.0)
    return jnp.where(keep, logits, _NEG_INF)


def sample_tokens(logits: jax.Array, st: SamplingState,
                  keys: jax.Array, steps: jax.Array,
                  want_logprobs=None, live=None,
                  ) -> tuple[jax.Array, jax.Array]:
    """logits [B, V] f32, keys [B] per-slot PRNG keys, steps [B] i32 ->
    (tokens [B] i32, logprobs_full [B, V] f32).

    `live` [B] bool: the rows whose token anyone reads (the decode batch's
    active slots; None = all). Only they decide whether the sampling and
    log-probability branches run: a slot that holds no request keeps the
    temperature and `want_logprobs` of its last occupant (1.0 if it never
    had one), and must not buy the batch two full-vocabulary sorts a step.

    Each row samples with fold_in(keys[b], steps[b]) — deterministic per
    request (and per `seed`) regardless of batch composition. Greedy where
    temperature == 0, otherwise penalized + tempered + top-k/top-p filtered
    categorical sampling.
    """
    logits = apply_penalties(logits, st)
    greedy_tokens = jnp.argmax(logits, axis=-1)

    def _sample(_):
        safe_temp = jnp.maximum(st.temperature, 1e-6)[:, None]
        scaled = logits / safe_temp
        scaled = _mask_top_k(scaled, st.top_k)
        scaled = _mask_top_p(scaled, st.top_p)
        sampled = jax.vmap(
            lambda key, step, row: jax.random.categorical(
                jax.random.fold_in(key, step), row))(keys, steps, scaled)
        return jnp.where(st.temperature <= 0.0, greedy_tokens, sampled)

    # The top-k/top-p masks cost full-vocab sorts; skip the whole branch at
    # runtime when every slot is greedy (the common serving case).
    sampled_rows = st.temperature > 0.0
    if live is not None:
        sampled_rows &= live
        if want_logprobs is not None:
            want_logprobs = want_logprobs & live
    tokens = jax.lax.cond(jnp.any(sampled_rows), _sample,
                          lambda _: greedy_tokens, operand=None)
    if want_logprobs is None:
        logprobs = jax.nn.log_softmax(logits, axis=-1)
    else:
        # Full-vocab log_softmax is bandwidth; skip unless requested.
        logprobs = jax.lax.cond(
            jnp.any(want_logprobs),
            lambda _: jax.nn.log_softmax(logits, axis=-1),
            lambda _: jnp.zeros_like(logits), operand=None)
    return tokens.astype(jnp.int32), logprobs


def record_tokens(token_counts: jax.Array, tokens: jax.Array,
                  active: jax.Array) -> jax.Array:
    """Scatter-add sampled tokens into the penalty histogram (active slots)."""
    B = token_counts.shape[0]
    return token_counts.at[jnp.arange(B), tokens].add(
        active.astype(jnp.int32))
