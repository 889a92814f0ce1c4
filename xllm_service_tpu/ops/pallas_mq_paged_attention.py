"""Pallas TPU multi-query paged attention (speculative-verify kernel).

The spec-verify forward attends a SHORT query block (last accepted token
+ drafts, S_q <= ~32) per sequence against that sequence's paged KV. The
XLA fallback gathers every sequence's full page span to dense tensors —
memory-bound at large batch*context. This kernel walks only the occupied
pages with the same double-buffered page-DMA structure as the decode
kernel (`pallas_paged_attention.py`), adding a per-query causal offset:
query s (at absolute position prefix + s) may attend key positions
<= prefix + s.

Assumes the block's own K/V have already been written into the pages
(true in `prefill_from_embeddings`: `write_prefill_kv` runs before
attention), so the pages hold the full context = prefix + block and the
kernel never needs the separate suffix K/V tensors.

Gated OFF by default (XLLM_MQ_PALLAS=1 to enable on TPU): correctness is
interpret-verified on CPU; Mosaic compilation must be validated on a real
chip before it becomes a default path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_page_dma import (
    NEG_INF as _NEG_INF,
    chunked_page_walk,
    flash_accumulate,
    masked_kv_f32,
    page_chunk_size,
)


def _kernel(page_table_ref, prefix_ref, block_ref,    # scalar prefetch
            q_ref,                                    # [1, Sq, n_q, hd]
            k_hbm, v_hbm,                             # pools in HBM/ANY
            o_ref,                                    # [1, Sq, n_q, hd]
            k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
            *, page_size: int, n_kv: int, group: int, scale: float,
            max_pages: int, chunk: int, s_q: int,
            pipeline_rows: bool):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    prefix = prefix_ref[b]
    blk = block_ref[b]                 # valid queries in this row's block
    ctx = prefix + blk                 # total written context

    def n_pages_of(row):
        row_ctx = prefix_ref[row] + block_ref[row]
        return jnp.minimum(pl.cdiv(row_ctx, page_size), max_pages)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(c, slot):
        span = chunk * page_size
        start = c * span
        # Query s sits at absolute position prefix + s; it may attend
        # keys at positions <= prefix + s. Rows are (s, g) flattened.
        key_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (s_q * group, span), 1)
        q_row_pos = prefix + jax.lax.broadcasted_iota(
            jnp.int32, (s_q * group, span), 0) // group
        mask = key_pos <= q_row_pos
        for kv in range(n_kv):
            # [Sq, G, hd] -> [Sq*G, hd] query rows for this KV head.
            qh = q_ref[0, :, kv * group:(kv + 1) * group, :] \
                .astype(jnp.float32).reshape(s_q * group, -1) * scale
            k, v = masked_kv_f32(k_buf, v_buf, slot, kv, start, ctx)
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [Sq*G, span]
            s = jnp.where(mask, s, _NEG_INF)
            flash_accumulate(
                slice(kv * s_q * group, (kv + 1) * s_q * group),
                s, v, m_scr, l_scr, acc_scr)

    chunked_page_walk(page_table_ref, b, nb, n_pages_of(b), n_pages_of,
                      chunk, k_hbm, v_hbm, k_buf, v_buf, sems, compute,
                      pipeline_rows)

    l = jnp.maximum(l_scr[:, :1], 1e-9)
    out = acc_scr[...] / l                         # [n_kv*Sq*G, hd]
    n_q = o_ref.shape[2]
    hd = o_ref.shape[3]
    # rows are (kv, s, g): reshape back to [Sq, n_q, hd].
    out = out.reshape(n_kv, s_q, group, hd).transpose(1, 0, 2, 3) \
        .reshape(s_q, n_q, hd)
    o_ref[0] = out.astype(o_ref.dtype)


def mq_paged_attention_pallas(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, page_table: jax.Array,
                              prefix_lens: jax.Array,
                              block_lens: jax.Array,
                              interpret: bool = False) -> jax.Array:
    """q: [B, Sq, n_q, hd] (short block per sequence); k/v_pages:
    [pages, n_kv, ps, hd] holding prefix AND block KV; page_table:
    [B, max_pages]; prefix_lens/block_lens: [B]. Returns [B, Sq, n_q, hd]
    — causal over absolute positions, identical to the XLA
    prefill_attention reference (tested).

    XLLM_PAGE_CHUNK / XLLM_PAGE_PIPELINE are resolved here, OUTSIDE
    jit, and passed static — a shape-keyed cache would silently pin the
    first-traced variant."""
    import os

    return _mq_impl(q, k_pages, v_pages, page_table, prefix_lens,
                    block_lens, chunk=page_chunk_size(page_table.shape[1]),
                    pipeline_rows=os.environ.get(
                        "XLLM_PAGE_PIPELINE", "") == "row",
                    interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "pipeline_rows",
                                             "interpret"))
def _mq_impl(q, k_pages, v_pages, page_table, prefix_lens, block_lens, *,
             chunk: int, pipeline_rows: bool = False,
             interpret: bool = False) -> jax.Array:
    B, s_q, n_q, hd = q.shape
    _, n_kv, page_size, _ = k_pages.shape
    max_pages = page_table.shape[1]
    group = n_q // n_kv
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_kernel, page_size=page_size, n_kv=n_kv,
                               group=group, scale=scale,
                               max_pages=max_pages, chunk=chunk, s_q=s_q,
                               pipeline_rows=pipeline_rows)
    rows = n_kv * s_q * group
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, s_q, n_q, hd), lambda b, pt, pf, bl: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, s_q, n_q, hd),
                               lambda b, pt, pf, bl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), k_pages.dtype),
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rows, 128), jnp.float32),   # m
            pltpu.VMEM((rows, 128), jnp.float32),   # l
            pltpu.VMEM((rows, hd), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, s_q, n_q, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, prefix_lens, block_lens, q, k_pages, v_pages)
