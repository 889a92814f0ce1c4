"""Sarathi mixed-step forward parity: one program
decoding the running batch while writing/attending a prefill sub-chunk
must be bit-equivalent to running decode_forward and the chunk write
separately — same decode logits, same KV pool contents."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.models.base import get_model_family, tiny_config
from xllm_service_tpu.models.gemma import gemma2_tiny_config
from xllm_service_tpu.ops.attention import prefill_attention, write_kv


def _setup(cfg, family):
    fam = get_model_family(family)
    params = fam.init_params(cfg, jax.random.PRNGKey(0))
    L, n_kv, ps, hd = cfg.num_layers, cfg.num_kv_heads, 16, cfg.head_dim
    pool = jax.random.normal(jax.random.PRNGKey(1),
                             (L, 2, 32, n_kv, ps, hd), cfg.dtype) * 0.1
    return fam, params, pool


@pytest.mark.parametrize("family,cfg", [
    ("llama", tiny_config(dtype=jnp.float32)),
    ("qwen2", tiny_config(dtype=jnp.float32, qkv_bias=True)),
    ("gemma", gemma2_tiny_config(dtype=jnp.float32)),
])
def test_mixed_step_matches_separate_programs(family, cfg):
    fam, params, pool = _setup(cfg, family)
    B, c, ps = 3, 16, 16
    # Decode rows: 3 sequences mid-generation on pages 1..6.
    dec_pt = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    dec_clens = jnp.asarray([5, 20, 17], jnp.int32)
    dec_pos = dec_clens - 1
    dec_tokens = jnp.asarray([7, 8, 9], jnp.int32)
    # Chunk: one prefilling sequence on pages 10..13, 24 tokens already
    # written, this sub-chunk carries 12 live tokens (4 padding rows).
    chunk_pt = jnp.asarray([[10, 11, 12, 13]], jnp.int32)
    start, valid = 24, 12
    chunk_tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, cfg.vocab_size, c), jnp.int32)
    chunk_pos = start + jnp.arange(c, dtype=jnp.int32)

    # Reference: plain decode on the SAME pool, then the chunk write via
    # the standalone prefill ops.
    ref_logits, ref_pool = jax.jit(fam.decode_forward, static_argnums=1)(
        params, cfg, dec_tokens, dec_pos, pool, dec_pt, dec_clens)

    def ref_chunk(pool):
        from xllm_service_tpu.models.llama import (_attn_opts, _embed,
                                                   _norm, _project_qkv)
        x = _embed(params, cfg, chunk_tokens)[None]      # [1, c, D]
        for l in range(cfg.num_layers):
            lp = jax.tree.map(lambda a, _l=l: a[_l], params["layers"])
            h = _norm(x, lp["input_norm"]["scale"], cfg)
            q, k, v = _project_qkv(lp, h, cfg, chunk_pos[None])
            pool = write_kv(
                pool, l, k, v, chunk_pt,
                jnp.asarray([start], jnp.int32),
                jnp.asarray([valid], jnp.int32))
            attn = prefill_attention(
                q, k, v, pool, l, chunk_pt,
                jnp.asarray([start], jnp.int32),
                jnp.asarray([valid], jnp.int32), **_attn_opts(cfg, l))
            from xllm_service_tpu.models.llama import _attn_mlp_residual
            x = _attn_mlp_residual(lp, x,
                                   attn.reshape(1, c, cfg.q_size), cfg)
        return pool

    ref_pool = jax.jit(ref_chunk)(ref_pool)

    mixed_logits, mixed_pool = jax.jit(
        fam.mixed_decode_chunk_forward, static_argnums=1)(
        params, cfg, dec_tokens, dec_pos, chunk_tokens, chunk_pos,
        pool, dec_pt, chunk_pt, dec_clens,
        jnp.asarray(start, jnp.int32), jnp.asarray(valid, jnp.int32))

    np.testing.assert_allclose(np.asarray(mixed_logits),
                               np.asarray(ref_logits), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(mixed_pool),
                               np.asarray(ref_pool), rtol=2e-5, atol=2e-5)


def test_mixed_step_empty_chunk_is_pure_decode():
    cfg = tiny_config(dtype=jnp.float32)
    fam, params, pool = _setup(cfg, "llama")
    dec_pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    dec_clens = jnp.asarray([5, 9], jnp.int32)
    dec_tokens = jnp.asarray([7, 8], jnp.int32)
    chunk_tokens = jnp.zeros((16,), jnp.int32)
    chunk_pt = jnp.asarray([[31]], jnp.int32)
    ref_logits, ref_pool = jax.jit(fam.decode_forward, static_argnums=1)(
        params, cfg, dec_tokens, dec_clens - 1, pool, dec_pt, dec_clens)
    logits, new_pool = jax.jit(
        fam.mixed_decode_chunk_forward, static_argnums=1)(
        params, cfg, dec_tokens, dec_clens - 1, chunk_tokens,
        jnp.arange(16, dtype=jnp.int32), pool, dec_pt, chunk_pt,
        dec_clens, jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=2e-5, atol=2e-5)
    # valid=0: nothing may land in the pool (garbage-page redirect).
    np.testing.assert_allclose(np.asarray(new_pool[:, :, 1:]),
                               np.asarray(ref_pool[:, :, 1:]),
                               rtol=2e-5, atol=2e-5)
