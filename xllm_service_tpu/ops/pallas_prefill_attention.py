"""Pallas TPU prefill attention: the decode kernel's page walk under a tile
of query rows and a causal limit.

`write_kv` has put the suffix's K and V into the pool before a prefill
attends (every caller hands `prefill_attention` the pool it returned), so
ALL keys of a sequence, cached prefix and fresh suffix alike, lie in its
page row: query row r of the suffix sits at position ``prefix_len + r`` and
sees the keys at positions ``<=`` its own. One call a layer does what the
XLA form (`ops/attention.prefill_attention`) does in a `cond` over a
`switch` of span-bucketed gathers:

- grid = (batch, query tiles). A tile is ``tq`` suffix rows of every query
  head (`page_walk.prefill_query_tile`); it walks the pages below its last
  row's position in chunks of 16 through the 2-slot ring of
  `chunked_page_walk`, runs of adjacent pages in one copy, and keeps the
  flash `m / l / acc` of all its rows in VMEM: no score leaves the chip's
  fast memory.
- K and V are read in the pool's type, once a KV head for its whole group
  of query heads: a tile's rows of one group are stacked head-major
  ``[group * tq, hd]`` in a scratch buffer and meet a chunk's keys in ONE
  product (in units of at most 512 rows, three passes a chunk over all
  units as decode passes over the heads). Operands go to the MXU as the
  pool holds them, float32 accumulation; scale, soft cap, mask, running max
  and sum stay float32 (`pallas_page_dma.head_scores` / `flash_softmax` /
  `flash_values`).
- what the mask empties is never walked: key chunks above a tile's causal
  limit (the walk ends at the tile's last row), and query tiles past
  ``seq_len`` (the bucket's padding rows), which write zeros and fetch
  nothing. ``prefix_lens`` and ``seq_lens`` are scalar-prefetch operands.
- soft cap and window ride as static parameters, as in the decode kernel; a
  window also starts the walk at the first chunk its first row can see.

The probabilities meet V as ONE term of the pool's type (bfloat16 rounds
them to 8 bits; a float32 pool rounds nothing), not the decode kernel's
three: a prefill's p . V is half its arithmetic, and the XLA form this
replaces rounds them the same way on the chip (its float32 einsum
multiplies in one bfloat16 pass, PERF.md §6 PR 35).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .page_walk import page_chunk_size, prefill_query_tile
from .pallas_page_dma import (
    NEG_INF as _NEG_INF,
    chunk_token_offsets,
    chunked_page_walk,
    flash_softmax,
    flash_values,
    head_scores,
    k_operand,
    kv_word_rows,
    token_offset_maps,
    v_operand,
    v_word_mask,
)

# What Mosaic may take of the chip's 128 MiB of VMEM for one call: a
# tile's state (`prefill_query_tile` holds it under 12 MiB), the ring, the
# pipelined q and output blocks, and a chunk's scores and probabilities.
_VMEM_LIMIT = 32 << 20
_UNIT_ROWS = 512        # rows of one (scores, softmax, values) chain


def _kernel(layer_ref, page_table_ref, prefix_lens_ref, seq_lens_ref,
            q_ref,                      # VMEM block [1, tq, n_heads * hd]
            pool_hbm,                   # the whole pool in HBM/ANY
            o_ref,                      # VMEM block [1, tq, n_heads * hd]
            k_buf, v_buf, sems,         # scratch: 2-slot chunk ring
            q_scr, m_scr, l_scr, acc_scr,
            *, page_size: int, group: int, scale: float, chunk: int,
            softcap: float, window: int):
    b = pl.program_id(0)
    tq = q_ref.shape[1]
    n_kv, hd = k_buf.shape[2], k_buf.shape[4]
    rows = group * tq                   # one KV head's query rows
    q0 = pl.program_id(1) * tq
    pre = prefix_lens_ref[b]
    seq = seq_lens_ref[b]

    @pl.when(q0 >= seq)
    def _padding():
        # Bucket padding: nobody reads these rows, but what flows on from
        # them must be finite.
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(q0 < seq)
    def _tile():
        k_hbm = pool_hbm.at[layer_ref[0], 0]    # [pages, n_kv, ps, hd]
        v_hbm = pool_hbm.at[layer_ref[0], 1]
        # Keys this tile can see: everything below its last valid row.
        n_keys = pre + jnp.minimum(q0 + tq, seq)

        # Head-major rows: head h's tq rows at [h * tq, (h + 1) * tq), so
        # that a KV head's whole group is one [rows, hd] operand.
        for h in range(n_kv * group):
            q_scr[h * tq:(h + 1) * tq, :] = q_ref[0, :, h * hd:(h + 1) * hd]
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        # A row's own position, the same for every head of the group. The
        # padding rows inside a live tile stop at the last valid key:
        # whatever lies behind it in a buffer was never fetched.
        q_pos = pre + q0 + jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tq)
        last = jnp.minimum(q_pos, n_keys - 1)

        span = chunk * page_size
        row_maps = token_offset_maps(chunk, page_size, (1, span), 1)
        word_rows = kv_word_rows(v_buf.dtype)
        word_maps = token_offset_maps(chunk, page_size,
                                      (span // word_rows, 1), 0,
                                      step=word_rows)

        # A KV head's rows in units of at most `_UNIT_ROWS`, and three
        # passes over all units a chunk (all scores, all softmaxes, all
        # values), as `attend_chunk` passes over the heads in decode. Unit
        # after unit, each product waits for the softmax before it; pass
        # by pass, independent products stand behind one another (on the
        # chip, 16 heads of 128 behind a 1536-token prefix: 133 -> 84 us a
        # layer, PERF.md §6 PR 40).
        n_units = -(-rows // _UNIT_ROWS)
        while rows % n_units or (rows // n_units) % 8:
            n_units += 1
        sb = rows // n_units
        units = [(kv, r0, slice(kv * rows + r0, kv * rows + r0 + sb))
                 for kv in range(n_kv) for r0 in range(0, rows, sb)]

        def compute(c, slot, d):
            start = c * span
            key_pos = start + chunk_token_offsets(row_maps, d)  # [1, span]

            def finish_scores(s, r0):
                if softcap > 0.0:
                    s = softcap * jnp.tanh(s / softcap)
                mask = key_pos <= last[r0:r0 + sb]           # [sb, span]
                if window > 0:
                    mask &= q_pos[r0:r0 + sb] - key_pos < window
                return jnp.where(mask, s, _NEG_INF)

            v_keep = v_word_mask(
                start + chunk_token_offsets(word_maps, d), n_keys,
                word_rows)
            scores = [finish_scores(head_scores(
                q_scr[rs, :], k_operand(k_buf, slot, kv), scale), r0)
                for kv, r0, rs in units]
            probs = [flash_softmax(rs, s, m_scr, l_scr)
                     for (_, _, rs), s in zip(units, scores)]
            for (kv, _, rs), (p, alpha) in zip(units, probs):
                flash_values(rs, p.astype(v_buf.dtype), alpha,
                             v_operand(v_buf, slot, kv, v_keep), acc_scr)

        c_lo = 0
        if window > 0:
            # The tile's first row sees nothing below its window.
            c_lo = (jnp.maximum(pre + q0 - window + 1, 0)
                    // page_size) // chunk
        n_pages = jnp.minimum(pl.cdiv(n_keys, page_size),
                              page_table_ref.shape[1])
        chunked_page_walk(page_table_ref, b, n_pages, chunk, k_hbm, v_hbm,
                          k_buf, v_buf, sems, compute, c_lo=c_lo)

        for h in range(n_kv * group):
            rs = slice(h * tq, (h + 1) * tq)
            l = jnp.maximum(l_scr[rs, :1], 1e-9)
            o_ref[0, :, h * hd:(h + 1) * hd] = (
                acc_scr[rs, :] / l).astype(o_ref.dtype)


def prefill_attention_pallas(q: jax.Array, pool: jax.Array,
                             layer: jax.Array, page_table: jax.Array,
                             prefix_lens: jax.Array, seq_lens: jax.Array,
                             interpret: bool = False,
                             scale: float | None = None,
                             softcap: float = 0.0,
                             window: int = 0) -> jax.Array:
    """q: [B, S, n_q, hd], the suffix's queries; pool: [L, 2, pages, n_kv,
    ps, hd] with the suffix's K and V already written at positions
    ``prefix_lens[b] + [0, seq_lens[b])`` of row b's pages; layer: [1] i32;
    page_table: [B, max_pages] i32. Returns [B, S, n_q, hd]; rows past
    ``seq_lens[b]`` are padding (zeros where a whole tile is).

    The caller has asked `page_walk.prefill_query_tile` that the shape has
    a tile (`ops/attention.prefill_attention_path`)."""
    B, S, n_q, hd = q.shape
    tq = prefill_query_tile(S, n_q, hd, q.dtype.itemsize)
    assert tq, ("no query tile", q.shape, q.dtype)
    out = _prefill_attention_impl(
        q.reshape(B, S, n_q * hd), pool, layer, page_table, prefix_lens,
        seq_lens, tq=tq, chunk=page_chunk_size(page_table.shape[1]),
        scale=float(scale) if scale is not None else hd ** -0.5,
        softcap=float(softcap), window=int(window), interpret=interpret)
    return out.reshape(B, S, n_q, hd)


@functools.partial(jax.jit, static_argnames=("tq", "chunk", "scale",
                                             "softcap", "window",
                                             "interpret"))
def _prefill_attention_impl(q: jax.Array, pool: jax.Array, layer: jax.Array,
                            page_table: jax.Array, prefix_lens: jax.Array,
                            seq_lens: jax.Array, *, tq: int, chunk: int,
                            scale: float, softcap: float = 0.0,
                            window: int = 0,
                            interpret: bool = False) -> jax.Array:
    B, S, width = q.shape
    _, _, _, n_kv, page_size, hd = pool.shape
    n_q = width // hd

    kernel = functools.partial(
        _kernel, page_size=page_size, group=n_q // n_kv, scale=scale,
        chunk=chunk, softcap=softcap, window=window)
    tile = pl.BlockSpec((1, tq, width), lambda b, i, *_: (b, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, S // tq),
        in_specs=[tile, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile,
        scratch_shapes=[
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), pool.dtype),
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_q * tq, hd), q.dtype),        # q, head-major
            pltpu.VMEM((n_q * tq, 128), jnp.float32),   # m
            pltpu.VMEM((n_q * tq, 128), jnp.float32),   # l
            pltpu.VMEM((n_q * tq, hd), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(layer, page_table, prefix_lens, seq_lens, q, pool)
