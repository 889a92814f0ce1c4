"""Pallas paged-attention kernel vs XLA reference (interpret mode on CPU),
and both against a plain per-layer form: the kernel and the gather read
`(pool, layer)` where the pool lies, `write_kv` appends into it in place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.ops.attention import (
    decode_attention_step,
    paged_attention_xla,
    write_kv,
)
from xllm_service_tpu.ops.page_walk import page_chunk_size, walk_run_counts
from xllm_service_tpu.ops.pallas_paged_attention import paged_attention_pallas

LAYERS = 3          # every case reads/writes one layer of a 3-layer pool


def _up(first, n):
    return list(range(first, first + n))


def _down(first, n):
    return list(range(first, first - n, -1))


# name -> (rows, kernel options); a row is (pool pages in table order,
# context length, (chunks walked, chunks fetched as a run)) over a 64-page
# pool, pages of 16 tokens and chunks of 16 pages.
RUN_TABLES = {
    "one-ascending-run": ([(_up(1, 40), 640, (3, 2)),
                           (_up(20, 32), 497, (2, 2))], {}),
    # the last page holds 5 tokens and lies FIRST in its reversed chunk
    "one-descending-run": ([(_down(63, 32), 31 * 16 + 5, (2, 2)),
                            (_down(40, 40), 640, (3, 2))], {}),
    "scattered": ([(list(range(1, 64, 2)), 512, (2, 0)),
                   (list(range(62, 0, -2)), 16 * 30 + 1, (2, 0))], {}),
    "broken-inside-a-chunk": (
        [(_up(1, 16) + _up(17, 6) + _up(30, 10), 512, (2, 1)),
         (_down(60, 16) + _down(44, 5) + _down(30, 11), 508, (2, 1))], {}),
    "turns-from-up-to-down": (
        [(_up(10, 16) + _down(60, 16) + _up(30, 4) + _down(29, 4), 640,
          (3, 2)),
         (_down(17, 16) + _up(33, 16), 506, (2, 2)),
         (_up(1, 8) + _down(40, 8), 256, (1, 0))], {}),
    "ends-in-a-partial-chunk": ([(_up(1, 21), 21 * 16 - 3, (2, 1)),
                                 (_down(63, 19), 19 * 16, (2, 1)),
                                 (_up(30, 9), 129, (1, 0))], {}),
    "one-page": ([(_up(7, 1), 5, (1, 0)), (_up(63, 1), 16, (1, 0))], {}),
    "touching-the-pools-last-page": ([(_up(48, 16), 256, (1, 1)),
                                      (_down(63, 16), 248, (1, 1)),
                                      (_up(32, 32), 512, (2, 2))], {}),
    "run-and-per-page-rows-in-one-batch": (
        [(_up(1, 16), 256, (1, 1)), (list(range(1, 33, 2)), 256, (1, 0)),
         ([], 0, (0, 0)), (_down(40, 32) + [3, 9], 16 * 33 + 7, (3, 2))],
        {}),
    # the window's lower edge falls inside a reversed chunk
    "descending-run-under-a-window": (
        [(_down(63, 32), 32 * 16 - 6, (2, 2)),
         (_up(1, 32), 32 * 16 - 6, (2, 2)),
         (_down(40, 16) + _up(41, 16), 512, (2, 2))],
        {"window": 100, "softcap": 30.0}),
}


def _setup(B=4, n_q=8, n_kv=4, hd=128, pages=32, ps=16, max_pages=6, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 2)
    pool = jax.random.normal(k1, (LAYERS, 2, pages, n_kv, ps, hd),
                             jnp.float32)
    q = jax.random.normal(k2, (B, n_q, hd), jnp.float32)
    # Distinct pages per row, nonzero ids (page 0 = garbage).
    pt = (jnp.arange(B * max_pages, dtype=jnp.int32).reshape(B, max_pages) + 1)
    return q, pool, pt


def _kernel(q, pool, layer, pt, cl, **kw):
    return paged_attention_pallas(q, pool, jnp.full((1,), layer, jnp.int32),
                                  pt, cl, interpret=True, **kw)


def _per_layer_attention(q, k_pages, v_pages, pt, cl, scale=None,
                         softcap=0.0, window=0, dtype=np.float64):
    """The plain per-layer form: one layer's K and V pages as arrays of
    their own, dense softmax per row in numpy, every operation in
    ``dtype`` (float32: what an exact float32 evaluation rounds away)."""
    q, k_pages, v_pages = (np.asarray(a.astype(jnp.float32), dtype)
                           for a in (q, k_pages, v_pages))
    pt, cl = np.asarray(pt), np.asarray(cl)
    B, n_q, hd = q.shape
    n_kv, ps = k_pages.shape[1], k_pages.shape[2]
    scale = dtype(hd ** -0.5 if scale is None else scale)
    softcap = dtype(softcap)
    out = np.zeros((B, n_q, hd), dtype)
    for b in range(B):
        c = int(cl[b])
        if c == 0:
            continue
        k = k_pages[pt[b]].transpose(0, 2, 1, 3).reshape(-1, n_kv, hd)[:c]
        v = v_pages[pt[b]].transpose(0, 2, 1, 3).reshape(-1, n_kv, hd)[:c]
        lo = max(c - window, 0) if window else 0
        for h in range(n_q):
            s = k[lo:, h // (n_q // n_kv)] @ (q[b, h] * scale)
            if softcap:
                s = softcap * np.tanh(s / softcap)
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[lo:, h // (n_q // n_kv)]
    return out


def _per_layer_append(pool, layer, k_new, v_new, pt, pos):
    """The plain per-layer write: token rows at (page, :, slot, :) of the
    layer's own K and V arrays; every other byte of the pool as it was."""
    want = np.array(pool)
    ps = want.shape[4]
    for b, p in enumerate(np.asarray(pos)):
        page = int(np.asarray(pt)[b, p // ps])
        want[layer, 0, page, :, p % ps, :] = np.asarray(k_new)[b]
        want[layer, 1, page, :, p % ps, :] = np.asarray(v_new)[b]
    return want


# name -> rows of (pool pages in table order, context length) over a
# 64-page pool: no two rows share a page, every long row ends mid-page and
# mid-chunk, and a short row FOLLOWS a long one, so its chunk's other
# sub-buffers are the long row's stale pages.
BF16_TABLES = {
    "runs-up": [(_up(1, 38), 37 * 16 + 5), (_up(60, 1), 3),
                (_up(40, 20), 20 * 16 - 9)],
    "runs-down": [(_down(63, 38), 37 * 16 + 5), (_down(1, 1), 16),
                  (_down(24, 20), 20 * 16 - 9)],
    "page-by-page": [(list(range(1, 64, 2)), 31 * 16 + 7), ([60], 9),
                     (list(range(58, 20, -2)), 18 * 16 + 1)],
}
BF16_HEADS = [(28, 4), (16, 2), (32, 8)]      # the benchmark's three cells


def _bf16_case(table, heads, poison=False, q_dtype=jnp.float32):
    """(q, pool, pt, cl) over a BFLOAT16 pool. ``q`` holds bfloat16
    numbers in ``q_dtype``: as float32 the kernel's output is float32 and
    shows the kernel's own error, not its output's rounding. ``poison``
    fills every pool position that no row may read (page 0, unlisted
    pages, a row's last page from its context on) with NaN and Inf."""
    rows = BF16_TABLES[table]
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    pool = np.asarray(jax.random.normal(
        k1, (LAYERS, 2, 64, heads[1], 16, 128), jnp.float32))
    pt = np.zeros((len(rows), 40), np.int32)            # page 0: garbage
    if poison:
        readable = np.zeros((64, 16), bool)
        for pages, ctx in rows:
            for i, page in enumerate(pages):
                readable[page, :max(0, min(16, ctx - 16 * i))] = True
        bad = np.where(np.arange(64 * 16).reshape(64, 16) % 2 == 0,
                       np.nan, np.inf).astype(np.float32)
        pool = np.where(readable[None, None, :, None, :, None], pool,
                        bad[None, None, :, None, :, None])
    for b, (pages, _) in enumerate(rows):
        pt[b, :len(pages)] = pages
    q = jax.random.normal(k2, (len(rows), heads[0], 128), jnp.float32)
    return (q.astype(jnp.bfloat16).astype(q_dtype),
            jnp.asarray(pool).astype(jnp.bfloat16), jnp.asarray(pt),
            jnp.asarray([ctx for _, ctx in rows], jnp.int32))


def _errors_against_float64(got, q, pool, pt, cl, **opts):
    """(the kernel's largest error, an exact float32 evaluation's) against
    the float64 evaluation of the same bfloat16 inputs."""
    args = (q, pool[1, 0], pool[1, 1], pt, cl)
    want = _per_layer_attention(*args, **opts)
    f32 = _per_layer_attention(*args, dtype=np.float32, **opts)
    return (np.abs(np.asarray(got, np.float64) - want).max(),
            np.abs(f32 - want).max())


class TestBfloat16Pool:
    """A bfloat16 pool's K and V meet the MXU as bfloat16, the
    probabilities as three bfloat16 terms: the result stays float32's."""

    @pytest.mark.parametrize("heads", BF16_HEADS, ids=str)
    @pytest.mark.parametrize("table", list(BF16_TABLES))
    def test_error_is_float32_rounding(self, table, heads):
        q, pool, pt, cl = _bf16_case(table, heads)
        got = _kernel(q, pool, 1, pt, cl)
        assert got.dtype == jnp.float32
        err, f32_err = _errors_against_float64(got, q, pool, pt, cl)
        assert err <= 2 * f32_err, (err, f32_err)

    @pytest.mark.parametrize("heads", BF16_HEADS, ids=str)
    @pytest.mark.parametrize("table", list(BF16_TABLES))
    def test_garbage_past_the_context_stays_out(self, table, heads):
        """NaN and Inf wherever the pool holds no row's tokens, so also in
        the ring's stale sub-buffers of the short row (and the interpreter
        starts the ring itself as NaN): the guard over V, a mask of its
        32-bit words, keeps 0 x garbage out and changes no other bit."""
        q, pool, pt, cl = _bf16_case(table, heads, poison=True)
        clean = _bf16_case(table, heads)[1]
        assert not np.isfinite(np.asarray(pool[1], np.float32)).all()
        got = np.asarray(_kernel(q, pool, 1, pt, cl))
        assert np.isfinite(got).all()
        np.testing.assert_array_equal(
            got, np.asarray(_kernel(q, clean, 1, pt, cl)))

    @pytest.mark.parametrize("heads", BF16_HEADS, ids=str)
    def test_a_bfloat16_query_rounds_nothing_but_its_output(self, heads):
        """The served types: q bfloat16 as the pool. The output is the
        float32 one rounded once: a float32 rounding flips one output in
        thousands across a bfloat16 boundary, a bfloat16 rounding inside
        (of the probabilities, say) moves four in ten."""
        q, pool, pt, cl = _bf16_case("runs-up", heads, q_dtype=jnp.bfloat16)
        got = _kernel(q, pool, 1, pt, cl)
        assert got.dtype == jnp.bfloat16
        wide = _kernel(q.astype(jnp.float32), pool, 1, pt, cl)
        got = np.asarray(got, np.float32)
        want = np.asarray(wide.astype(jnp.bfloat16), np.float32)
        assert (got != want).mean() < 1e-3
        np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=0)

    @pytest.mark.parametrize("opts", [
        {"softcap": 30.0}, {"window": 100},
        {"softcap": 50.0, "window": 33, "scale": 0.0625},
    ], ids=["softcap", "window", "softcap-window-scale"])
    @pytest.mark.parametrize("table", ["runs-down", "page-by-page"])
    def test_gemma2_options_on_a_bfloat16_pool(self, table, opts):
        """softcap, window and the explicit scale act on the float32
        scores; a window's lower edge falls inside a reversed chunk."""
        q, pool, pt, cl = _bf16_case(table, (16, 2), poison=True)
        got = _kernel(q, pool, 1, pt, cl, **opts)
        err, f32_err = _errors_against_float64(got, q, pool, pt, cl, **opts)
        assert err <= 2 * f32_err, (err, f32_err)


class TestPallasPagedAttention:
    @pytest.mark.parametrize("context_lens", [
        [96, 96, 96, 96],          # full pages
        [1, 17, 33, 90],           # ragged, partial pages
        [5, 96, 0, 50],            # includes an inactive row (ctx 0)
        [0, 0, 0, 7],              # leading empty rows
        [64, 0, 0, 0],             # trailing empty rows
    ])
    def test_matches_xla(self, context_lens):
        q, pool, pt = _setup()
        cl = jnp.asarray(context_lens, jnp.int32)
        ref = paged_attention_xla(q, pool, 1, pt, cl)
        got = _kernel(q, pool, 1, pt, cl)
        # Rows with ctx 0 are undefined in both paths; compare active rows.
        for b, c in enumerate(context_lens):
            if c > 0:
                np.testing.assert_allclose(np.asarray(got[b]),
                                           np.asarray(ref[b]),
                                           rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("name", list(RUN_TABLES))
    def test_run_tables_match_xla(self, name):
        """The walk fetches a full chunk of 16 adjacent pool pages, up or
        down, with one DMA a side and every other page alone: each table
        gives the gather's answer, and the host's rule (what the engine's
        telemetry counts) names the path each chunk takes."""
        rows, opts = RUN_TABLES[name]
        width = 40
        q, pool, _ = _setup(B=len(rows), pages=64, max_pages=width)
        pt = np.zeros((len(rows), width), np.int32)     # page 0: garbage
        for b, (pages, _, _) in enumerate(rows):
            pt[b, :len(pages)] = pages
        cl = jnp.asarray([ctx for _, ctx, _ in rows], jnp.int32)
        assert page_chunk_size(width) == 16
        for b, (pages, ctx, want) in enumerate(rows):
            assert walk_run_counts(pt[b], -(-ctx // 16), 16) == want, b
        ref = paged_attention_xla(q, pool, 1, jnp.asarray(pt), cl, **opts)
        got = _kernel(q, pool, 1, jnp.asarray(pt), cl, **opts)
        for b, (_, ctx, _) in enumerate(rows):
            if ctx > 0:
                np.testing.assert_allclose(np.asarray(got[b]),
                                           np.asarray(ref[b]),
                                           rtol=2e-5, atol=2e-5)

    def test_a_narrow_table_clamps_the_chunk(self):
        """A table narrower than 16 pages: the chunk is the table, and a
        row that fills it with adjacent pages is still one run."""
        assert [page_chunk_size(n) for n in (1, 6, 16, 96)] == [1, 6, 16, 16]
        q, pool, pt = _setup()              # rows 1..6, 7..12, ...: runs
        assert walk_run_counts(np.asarray(pt[0]), 6, 6) == (1, 1)
        assert walk_run_counts(np.asarray(pt[0]), 5, 6) == (1, 0)
        down = pt[:, ::-1]
        cl = jnp.asarray([96, 90, 81, 96], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(_kernel(q, pool, 2, down, cl)),
            np.asarray(paged_attention_xla(q, pool, 2, down, cl)),
            rtol=2e-5, atol=2e-5)

    def test_span_bucketed_xla_gather_parity(self, monkeypatch):
        """The pow2 span ladder the accelerator backend uses (the CPU
        suite keeps the single full-span branch for compile time), steered
        through the `_backend` hook: every ladder rung must match the
        full-span gather, including at occupancies that select the
        shortest span."""
        from xllm_service_tpu.ops import attention

        q, pool, pt = _setup()
        for cls in ([8, 12, 4, 16],              # shortest span
                    [40, 41, 33, 50],            # middle rung
                    [96, 96, 96, 96]):           # full span
            cl = jnp.asarray(cls, jnp.int32)
            ref = paged_attention_xla(q, pool, 0, pt, cl)
            with monkeypatch.context() as m:
                m.setattr(attention, "_backend", lambda: "tpu")
                hlo = jax.jit(paged_attention_xla, static_argnums=2).lower(
                    q, pool, 0, pt, cl).as_text()
                got = paged_attention_xla(q, pool, 0, pt, cl)
            assert "case" in hlo          # the ladder's switch is traced
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                       rtol=2e-6, atol=2e-6)

    @pytest.mark.parametrize("opts", [
        {"softcap": 30.0},                       # gemma-2 logit cap
        {"window": 40},                          # sliding-window layer
        {"scale": 0.0883883},                    # query_pre_attn_scalar
        {"softcap": 50.0, "window": 33, "scale": 0.0625},
    ])
    def test_gemma2_options_match_xla(self, opts):
        """softcap / sliding window / explicit query scale are static
        kernel params now — gemma-2 decode must route through the kernel
        with XLA-exact numerics."""
        q, pool, pt = _setup()
        cl = jnp.asarray([96, 41, 8, 64], jnp.int32)
        ref = paged_attention_xla(q, pool, 1, pt, cl, **opts)
        got = _kernel(q, pool, 1, pt, cl, **opts)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_grouping(self):
        q, pool, pt = _setup(n_q=16, n_kv=2)
        cl = jnp.asarray([40, 96, 8, 64], jnp.int32)
        ref = paged_attention_xla(q, pool, 1, pt, cl)
        got = _kernel(q, pool, 1, pt, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
    @pytest.mark.parametrize("heads", [(8, 8), (16, 2)],
                             ids=["mha", "gqa"])
    @pytest.mark.parametrize("opts", [
        {}, {"softcap": 50.0, "window": 33}], ids=["plain", "softcap-window"])
    def test_pool_layer_reads_match_per_layer_form(self, layer, heads, opts):
        """The kernel on (pool, layer) and the XLA gather on (pool, layer)
        both equal the per-layer form run on that layer's own K and V
        arrays — first, middle and last layer, MHA and GQA, with and
        without the gemma-2 statics."""
        q, pool, pt = _setup(n_q=heads[0], n_kv=heads[1])
        cl = jnp.asarray([96, 41, 8, 64], jnp.int32)
        want = _per_layer_attention(q, pool[layer, 0], pool[layer, 1], pt,
                                    cl, **opts)
        for got in (_kernel(q, pool, layer, pt, cl, **opts),
                    paged_attention_xla(q, pool, layer, pt, cl, **opts)):
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("layer", [0, 1, LAYERS - 1])
    @pytest.mark.parametrize("heads", [(8, 8), (16, 2)],
                             ids=["mha", "gqa"])
    @pytest.mark.parametrize("opts", [
        {}, {"softcap": 50.0, "window": 33}], ids=["plain", "softcap-window"])
    def test_decode_step_appends_in_place(self, layer, heads, opts,
                                          monkeypatch):
        """The one decode append+attend path, routed through the kernel:
        attention output equals the per-layer form after a per-layer row
        write, and the pool holds the same bytes — the written layer's
        token rows and nothing else, in any layer."""
        monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "1")
        q, pool, pt = _setup(n_q=heads[0], n_kv=heads[1])
        B, n_kv, hd = 4, heads[1], 128
        for prev in ([10, 20, 30, 40],   # mid-page appends
                     [0, 16, 31, 95]):   # first token, page edges, last slot
            cl = jnp.asarray(prev, jnp.int32) + 1
            k_new = jax.random.normal(jax.random.PRNGKey(9), (B, n_kv, hd))
            v_new = jax.random.normal(jax.random.PRNGKey(10), (B, n_kv, hd))
            want_pool = _per_layer_append(pool, layer, k_new, v_new, pt, prev)
            want = _per_layer_attention(q, want_pool[layer, 0],
                                        want_pool[layer, 1], pt, cl, **opts)
            got, got_pool = decode_attention_step(q, k_new, v_new, pool,
                                                  layer, pt, cl, **opts)
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_array_equal(np.asarray(got_pool), want_pool)

    @pytest.mark.parametrize("B,S", [(1, 40), (2, 16), (3, 7), (2, 1)])
    def test_write_kv_runs_match_per_token_writes(self, B, S):
        """Prefill suffixes, verify blocks and chunks through the one
        writer: runs starting mid-page, ending mid-page, padded
        (lens < S) or empty land exactly where per-token row writes put
        them; page 0 and every other layer keep their bytes."""
        rng = np.random.default_rng(S)
        _, pool, pt = _setup(B=B)
        n_kv, hd = pool.shape[3], pool.shape[5]
        k = jnp.asarray(rng.normal(size=(B, S, n_kv, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, n_kv, hd)), jnp.float32)
        start = jnp.asarray(rng.integers(0, 96 - S + 1, size=B), jnp.int32)
        lens = jnp.asarray(rng.integers(0, S + 1, size=B), jnp.int32)
        want = np.array(pool)
        for b in range(B):
            for j in range(int(lens[b])):
                p = int(start[b]) + j
                want[2, 0, pt[b, p // 16], :, p % 16, :] = k[b, j]
                want[2, 1, pt[b, p // 16], :, p % 16, :] = v[b, j]
        got = jax.jit(write_kv, static_argnums=1)(pool, 2, k, v, pt, start,
                                                  lens)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_after_decode_write(self):
        """End-to-end shape: write one token then attend, both paths."""
        q, pool, pt = _setup()
        B, n_kv, hd = 4, 4, 128
        cl_prev = jnp.asarray([10, 20, 30, 40], jnp.int32)
        k_new = jax.random.normal(jax.random.PRNGKey(9), (B, n_kv, hd))
        v_new = jax.random.normal(jax.random.PRNGKey(10), (B, n_kv, hd))
        pool = write_kv(pool, 1, k_new[:, None], v_new[:, None], pt,
                        cl_prev, jnp.ones_like(cl_prev))
        cl = cl_prev + 1
        ref = paged_attention_xla(q, pool, 1, pt, cl)
        got = _kernel(q, pool, 1, pt, cl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
