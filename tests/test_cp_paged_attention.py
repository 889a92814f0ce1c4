"""Context-parallel paged decode attention: page pool sharded over the
seq axis, flash-stats psum merge — must equal single-device paged
attention exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.ops.attention import paged_attention_xla
from xllm_service_tpu.ops.cp_paged_attention import cp_paged_attention
from xllm_service_tpu.parallel.mesh import MeshConfig, build_mesh


def make_case(B=4, pages=32, n_kv=2, ps=16, hd=32, H=4, seed=0):
    rng = np.random.default_rng(seed)
    k_pages = jnp.asarray(rng.normal(size=(pages, n_kv, ps, hd)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(pages, n_kv, ps, hd)),
                          jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    # Page tables deliberately interleave pages from every shard range.
    pt = jnp.asarray(rng.permutation(pages)[:B * 4].reshape(B, 4)
                     .astype(np.int32))
    clens = jnp.asarray(rng.integers(5, 4 * ps, B).astype(np.int32))
    return q, k_pages, v_pages, pt, clens


class TestCpPagedAttention:
    @pytest.mark.parametrize("sp", [2, 4])
    def test_matches_single_device(self, sp):
        q, kp, vp, pt, clens = make_case()
        want = paged_attention_xla(q, jnp.stack([kp, vp])[None], 0, pt, clens)
        mesh = build_mesh(MeshConfig(seq=sp), devices=jax.devices()[:sp])
        with mesh:
            got = jax.jit(lambda *a: cp_paged_attention(
                *a, mesh=mesh))(q, kp, vp, pt, clens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("H,n_kv", [(4, 4), (8, 2)])
    def test_kernel_path_matches_xla(self, monkeypatch, H, n_kv):
        """The Pallas partial-stats body (chunked page DMA over owned
        pages only) must match the dense XLA body exactly — interpret
        mode exercises the REAL kernel routing hermetically."""
        import xllm_service_tpu.ops.cp_paged_attention as cpmod

        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        calls = {"n": 0}
        real = cpmod._paged_partial_pallas

        def spy(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(cpmod, "_paged_partial_pallas", spy)
        q, kp, vp, pt, clens = make_case(hd=128, H=H, n_kv=n_kv, seed=5)
        want = paged_attention_xla(q, jnp.stack([kp, vp])[None], 0, pt, clens)
        mesh = build_mesh(MeshConfig(seq=4), devices=jax.devices()[:4])
        with mesh:
            got = cp_paged_attention(q, kp, vp, pt, clens, mesh=mesh)
        assert calls["n"] > 0, "Pallas partial body was not selected"
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_and_garbage_pages(self):
        """GQA head grouping + rows whose page tables include the garbage
        page (id 0, present in every inactive slot's table)."""
        q, kp, vp, pt, clens = make_case(H=8, n_kv=2, seed=3)
        pt = pt.at[0].set(jnp.array([0, 0, 0, 0], jnp.int32))
        clens = clens.at[0].set(1)
        want = paged_attention_xla(q, jnp.stack([kp, vp])[None], 0, pt, clens)
        mesh = build_mesh(MeshConfig(seq=4), devices=jax.devices()[:4])
        with mesh:
            got = cp_paged_attention(q, kp, vp, pt, clens, mesh=mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("pool_dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32-pool", "bfloat16-pool"])
    def test_kernel_path_on_run_tables(self, monkeypatch, pool_dtype):
        """Owned entries that fill a chunk (the 16-wide table) with pages
        adjacent in the shard's pool, up or down, go through the walk's run
        copy: ascending, descending with a partly filled last page; a row
        that crosses the two shards and a scattered one go page by page.
        A bfloat16 pool's K and V reach the products as bfloat16 (the
        guard over V is then per 32-bit word, two rows each) and the
        answer stays the float32 one."""
        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        q, kp, vp, _, _ = make_case(pages=64, hd=128, H=8, n_kv=2, seed=7)
        kp, vp = kp.astype(pool_dtype), vp.astype(pool_dtype)
        q = q.astype(pool_dtype).astype(jnp.float32)
        rng = np.random.default_rng(7)
        pt = jnp.asarray(np.stack([
            np.arange(1, 17), np.arange(60, 44, -1), np.arange(26, 42),
            rng.permutation(64)[:16]]).astype(np.int32))
        clens = jnp.asarray([256, 15 * 16 + 3, 16 * 16 - 9, 200], jnp.int32)
        want = paged_attention_xla(q, jnp.stack([kp, vp])[None], 0, pt, clens)
        mesh = build_mesh(MeshConfig(seq=2), devices=jax.devices()[:2])
        with mesh:
            got = cp_paged_attention(q, kp, vp, pt, clens, mesh=mesh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
