"""Pallas TPU paged-attention decode kernel.

The engine's hottest op (SURVEY.md §7.3: "Pallas ragged paged-attention
kernel quality drives the tok/s/chip north star"). One query token per
sequence attends over that sequence's KV pages, located via its page table.

Design (v2 — manual double-buffered DMA):
- grid = (batch,). The whole KV pool `[L, 2, pages, n_kv, ps, hd]` stays in
  HBM (`memory_space=ANY`) as ONE operand and the layer to read is a
  scalar-prefetch operand: DMA sources are `pool.at[layer, 0|1, page]`,
  so no caller ever slices a layer out for the kernel (an operand needs a
  buffer of its own — a sliced layer is a copy of it, per layer per step).
  A scalar and not a static int: every layer's call is then the same
  traced kernel. The kernel
  walks only the pages the sequence actually occupies (`cdiv(ctx, ps)` —
  a *dynamic* trip count, unlike a grid dimension) in chunks of 16 through
  a 2-slot VMEM scratch ring, chunk i+1 loading while chunk i computes. A
  full chunk whose pool pages are adjacent, up or down, is one DMA per
  side; other pages are one each (ops/pallas_page_dma.py).
- layer id, page table + context lengths are scalar-prefetch operands
  (SMEM) so DMA source addresses are computable before compute starts.
- online-softmax accumulation (flash-style m/l/acc) in VMEM scratch; GQA
  via static loops over KV heads with G query rows each, three passes a
  chunk (all scores, all softmaxes, all values: `attend_chunk`).
- K and V go to the MXU as the pool holds them: for a bfloat16 pool the
  bfloat16 `q` rows against bfloat16 K (scale, softcap and mask on the
  float32 scores), and the float32 probabilities as three bfloat16 terms
  against bfloat16 V: every product exact, float32 accumulation, no
  float32 copy of K or V. A float32 pool keeps float32 operands.
- KV page layout ``[..., num_pages, n_kv, page_size, head_dim]``: one page
  is a contiguous (n_kv, ps, hd) block whose minor dims match the bf16
  (16, 128) tile.

vs the v1 grid-over-pages version: no DMA for garbage pages past the
context length (the old version fetched all `max_pages` table slots), and
~B× fewer grid steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .page_walk import page_chunk_size
from .pallas_page_dma import (
    NEG_INF as _NEG_INF,
    attend_chunk,
    chunk_token_offsets,
    chunked_page_walk,
    kv_word_rows,
    token_offset_maps,
    v_word_mask,
)


def _kernel(layer_ref, page_table_ref, context_lens_ref,   # SMEM prefetch
            q_ref,                              # VMEM block [1, n_q, hd]
            pool_hbm,                           # the whole pool in HBM/ANY
            o_ref,                              # VMEM block [1, n_q, hd]
            k_buf, v_buf, sems,                 # scratch: 2-slot chunk ring
            m_scr, l_scr, acc_scr,
            *, page_size: int, group: int, scale: float,
            max_pages: int, chunk: int, softcap: float, window: int):
    b = pl.program_id(0)
    ctx = context_lens_ref[b]
    k_hbm = pool_hbm.at[layer_ref[0], 0]        # [pages, n_kv, ps, hd] views
    v_hbm = pool_hbm.at[layer_ref[0], 1]

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    span = chunk * page_size
    row_maps = token_offset_maps(chunk, page_size, (1, span), 1)
    word_rows = kv_word_rows(v_buf.dtype)
    word_maps = token_offset_maps(chunk, page_size, (span // word_rows, 1),
                                  0, step=word_rows)

    def compute(c, slot, d):
        start = c * span
        # A chunk fetched downwards lies in the buffer in reverse page
        # order; the masks follow it, nothing else depends on key order.
        token_pos = start + chunk_token_offsets(row_maps, d)
        mask = token_pos < ctx
        if window > 0:
            # gemma-2 sliding window: the query sits at position ctx-1,
            # so visible keys are >= ctx - window (matches the XLA path).
            mask &= token_pos >= ctx - window

        def finish_scores(s):
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            return jnp.where(mask, s, _NEG_INF)

        v_keep = v_word_mask(start + chunk_token_offsets(word_maps, d),
                             ctx, word_rows)
        attend_chunk(q_ref[0], group, k_buf, v_buf, slot, v_keep, scale,
                     finish_scores, m_scr, l_scr, acc_scr)

    c_lo = 0
    if window > 0:
        # Sliding window: pages wholly below ctx - window are never
        # visible — start the walk at the first visible page's chunk.
        c_lo = (jnp.maximum(ctx - window, 0) // page_size) // chunk

    n_pages = jnp.minimum(pl.cdiv(context_lens_ref[b], page_size),
                          max_pages)
    chunked_page_walk(page_table_ref, b, n_pages, chunk, k_hbm, v_hbm,
                      k_buf, v_buf, sems, compute, c_lo=c_lo)

    l = jnp.maximum(l_scr[:, :1], 1e-9)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, pool: jax.Array,
                           layer: jax.Array, page_table: jax.Array,
                           context_lens: jax.Array,
                           interpret: bool = False,
                           scale: float | None = None,
                           softcap: float = 0.0,
                           window: int = 0) -> jax.Array:
    """q: [B, n_q, hd]; pool: [L, 2, pages, n_kv, ps, hd]; layer: [1] i32,
    the layer of the pool to read; page_table: [B, max_pages] i32;
    context_lens: [B] i32 (incl. the new token, whose K/V must already be
    written). Returns [B, n_q, hd].

    scale/softcap/window cover the gemma-2 extras (explicit query scale,
    score soft-capping, sliding window) so that family decodes through
    this kernel instead of the full-span XLA gather.
    """
    return _paged_attention_impl(q, pool, layer, page_table,
                                 context_lens,
                                 chunk=page_chunk_size(page_table.shape[1]),
                                 scale=(float(scale)
                                        if scale is not None else None),
                                 softcap=float(softcap),
                                 window=int(window),
                                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "scale", "softcap",
                                             "window", "interpret"))
def _paged_attention_impl(q: jax.Array, pool: jax.Array,
                          layer: jax.Array, page_table: jax.Array,
                          context_lens: jax.Array, *, chunk: int,
                          scale: float | None = None,
                          softcap: float = 0.0, window: int = 0,
                          interpret: bool = False) -> jax.Array:
    B, n_q, hd = q.shape
    _, _, _, n_kv, page_size, _ = pool.shape
    max_pages = page_table.shape[1]
    group = n_q // n_kv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, page_size=page_size,
                               group=group, scale=scale,
                               max_pages=max_pages, chunk=chunk,
                               softcap=softcap, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b, ly, pt, cl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, n_q, hd),
                               lambda b, ly, pt, cl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), pool.dtype),
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_q, 128), jnp.float32),   # m
            pltpu.VMEM((n_q, 128), jnp.float32),   # l
            pltpu.VMEM((n_q, hd), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, n_q, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, page_table, context_lens, q, pool)
