"""Multi-query paged attention kernel (spec-verify path) vs the XLA
prefill_attention reference, interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.ops.attention import (
    prefill_attention,
    write_kv,
)
from xllm_service_tpu.ops.pallas_mq_paged_attention import (
    mq_paged_attention_pallas,
)


def _setup(B=3, s_q=5, n_q=8, n_kv=4, hd=128, pages=32, ps=16,
           max_pages=6, seed=0):
    """Build pools where each row's prefix AND block KV are written (the
    verify path's invariant), plus the matching dense reference inputs."""
    rng = np.random.default_rng(seed)
    pool = jnp.zeros((1, 2, pages, n_kv, ps, hd), jnp.float32)
    pt = (jnp.arange(B * max_pages, dtype=jnp.int32)
          .reshape(B, max_pages) + 1)
    prefix = jnp.asarray(rng.integers(1, 3 * ps, B).astype(np.int32))
    block = jnp.asarray(rng.integers(1, s_q + 1, B).astype(np.int32))

    # Prefix KV written page-wise.
    pk = jnp.asarray(rng.normal(size=(B, 3 * ps, n_kv, hd)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(B, 3 * ps, n_kv, hd)), jnp.float32)
    pool = write_kv(pool, 0, pk, pv, pt, jnp.zeros((B,), jnp.int32), prefix)
    # Block KV written at positions prefix..prefix+block.
    bk = jnp.asarray(rng.normal(size=(B, s_q, n_kv, hd)), jnp.float32)
    bv = jnp.asarray(rng.normal(size=(B, s_q, n_kv, hd)), jnp.float32)
    pool = write_kv(pool, 0, bk, bv, pt, prefix, block)
    q = jnp.asarray(rng.normal(size=(B, s_q, n_q, hd)), jnp.float32)
    return q, bk, bv, pool, pt, prefix, block


class TestMqPagedAttention:
    @pytest.mark.parametrize("seed", [0, 3])
    def test_matches_prefill_attention(self, seed):
        q, bk, bv, pool, pt, prefix, block = _setup(seed=seed)
        ref = prefill_attention(q, bk, bv, pool, 0, pt, prefix, block)
        got = mq_paged_attention_pallas(q, pool[0, 0], pool[0, 1], pt,
                                        prefix, block, interpret=True)
        # Compare only valid (row, s) queries — padding rows are undefined
        # in both paths.
        for b in range(q.shape[0]):
            for s in range(int(block[b])):
                np.testing.assert_allclose(
                    np.asarray(got[b, s]), np.asarray(ref[b, s]),
                    rtol=2e-5, atol=2e-5)

    def test_single_query_degenerates_to_decode_semantics(self):
        """s_q=1, block=1: behaves like decode attention over
        context = prefix + 1."""
        from xllm_service_tpu.ops.attention import paged_attention_xla

        q, bk, bv, pool, pt, prefix, block = _setup(s_q=1, seed=7)
        B = q.shape[0]
        one = jnp.ones((B,), jnp.int32)
        got = mq_paged_attention_pallas(q, pool[0, 0], pool[0, 1], pt,
                                        prefix, one, interpret=True)
        ref = paged_attention_xla(q[:, 0], pool, 0, pt, prefix + 1)
        np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
