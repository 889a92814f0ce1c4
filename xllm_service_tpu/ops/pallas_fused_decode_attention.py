"""Fused decode step: KV write + paged attention in ONE Pallas kernel.

The unfused decode path does, per layer: an XLA scatter of the new token's
K/V into the paged pool (`ops/attention.write_decode_kv`), then the paged
attention kernel re-reads those pages from HBM. That costs an extra HBM
round-trip per layer per step (read-modify-write of the touched page plus
the kernel's re-read) on the most bandwidth-bound program in the engine
(SURVEY.md §7.3 hard part #2 — decode is weights+KV bound).

This kernel fuses the append:
- the new token's K/V arrive as VMEM operands ``[B, n_kv, hd]``;
- the append is a whole-page read-modify-write: at grid-step start the
  kernel DMAs the tail page ``page_table[b, pos // ps]`` (where ``pos =
  context_lens[b] - 1``; context_lens INCLUDE the new token) into VMEM —
  Mosaic tiles HBM memrefs (8,128) over (ps, hd) too, so a single-slot
  [n_kv, 1, hd] window can't be DMA'd directly, but page-granular slices
  cut only the major dim and are always aligned. After the page walk the
  new row is spliced in with a vector select and the page DMA'd back
  (~2x 64KB per step vs the multi-MB walk — noise, and it replaces the
  separate XLA scatter's own read-modify-write);
- attention walks only the *previous* ``ctx - 1`` tokens from HBM pages
  (the write-back can race the walk's read of the same page — the
  written slot is masked out of every read, so a torn read is never
  used; the rest of the written page is bit-identical to what was read);
- the new token's attention contribution is computed directly from the
  VMEM operands and merged into the online softmax at the end — exact,
  and it never waits on the HBM write;
- the write-back is waited at the end of the grid step; the pools are
  input/output-aliased so the append is in place.
- The tail page is PRIVATE to the sequence (the engine allocates a fresh
  page at each boundary and prefix-cache sharing only covers full hash
  blocks), so the RMW never clobbers another sequence's data; inactive
  rows RMW the garbage page 0, where torn whole-page writes are
  harmless (nothing reads it).

Per-sequence pages are disjoint (the engine owns the page allocator), so
concurrent grid steps never write the same live slot; padded/finished
rows redirect to the reserved garbage page 0, where torn writes are
harmless (same invariant as `write_decode_kv(mode="drop")`).

Gated behind XLLM_KV_WRITEBACK=fused (see `ops/attention.decode_attention_step`)
until Mosaic-validated + measured on a real chip; interpret-mode parity is
covered by tests/test_pallas_attention.py (test_fused_decode_step_*).
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


def _kernel(page_table_ref, context_lens_ref,   # scalar prefetch (SMEM)
            q_ref,                              # VMEM block [1, n_q, hd]
            k_new_ref, v_new_ref,               # VMEM blocks [1, n_kv, hd]
            k_in, v_in,                         # full pools (HBM/ANY, aliased)
            o_ref,                              # VMEM block [1, n_q, hd]
            k_out, v_out,                       # same buffers as k_in/v_in
            k_buf, v_buf, sems, wsems,          # scratch
            k_pg, v_pg,                         # tail-page RMW staging
            m_scr, l_scr, acc_scr,
            *, page_size: int, n_kv: int, group: int, scale: float,
            max_pages: int, chunk: int, pipeline_rows: bool):
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    ctx = context_lens_ref[b]
    pos = jnp.maximum(ctx - 1, 0)               # the new token's position
    # Kick the tail-page READ DMAs first so they overlap the page walk
    # (see module docstring: whole-page RMW is the only tiling-aligned
    # way to land one token's row in the (8,128)-tiled HBM pool).
    wpage = page_table_ref[b, jnp.minimum(pos // page_size, max_pages - 1)]
    slot = pos % page_size
    pltpu.make_async_copy(k_in.at[wpage], k_pg, wsems.at[0, 0]).start()
    pltpu.make_async_copy(v_in.at[wpage], v_pg, wsems.at[0, 1]).start()

    ctx_prev = pos                              # tokens already in the pool

    def n_pages_of(row):
        # The walk covers only the PREVIOUS tokens (ctx - 1); the new
        # token's contribution merges from VMEM below. Cross-row
        # prefetch uses the same rule for row b+1, so its guard set
        # matches the waits row b+1 will issue.
        prev = jnp.maximum(context_lens_ref[row] - 1, 0)
        return jnp.minimum(pl.cdiv(prev, page_size), max_pages)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale           # [n_q, hd]

    def compute(c, slot_):
        span = chunk * page_size
        start = c * span
        token_pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (1, span), 1)
        # Bound the walk at ctx_prev: the new token's slot (possibly
        # racing the in-flight append DMA) is masked out of every
        # read, both in scores and in the V zeroing inside
        # masked_kv_f32.
        mask = token_pos < ctx_prev
        for kv in range(n_kv):
            qh = q[kv * group:(kv + 1) * group, :]     # [G, hd]
            k, v = masked_kv_f32(k_buf, v_buf, slot_, kv, start,
                                 ctx_prev)
            s = jax.lax.dot_general(
                qh, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)    # [G, span]
            s = jnp.where(mask, s, _NEG_INF)
            flash_accumulate(slice(kv * group, (kv + 1) * group),
                             s, v, m_scr, l_scr, acc_scr)

    chunked_page_walk(page_table_ref, b, nb, n_pages_of(b), n_pages_of,
                      chunk, k_in, v_in, k_buf, v_buf, sems, compute,
                      pipeline_rows)

    # Merge the new token's contribution straight from VMEM (it is always
    # attended: position ctx-1 < ctx).
    k_new = k_new_ref[0].astype(jnp.float32)           # [n_kv, hd]
    v_new = v_new_ref[0].astype(jnp.float32)
    for kv in range(n_kv):
        rows = slice(kv * group, (kv + 1) * group)
        qh = q[rows, :]                                # [G, hd]
        s = jax.lax.dot_general(
            qh, k_new[kv:kv + 1], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [G, 1]
        flash_accumulate(rows, s, v_new[kv:kv + 1], m_scr, l_scr, acc_scr)

    l = jnp.maximum(l_scr[:, :1], 1e-9)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)

    # Splice the new row into the staged tail page and write it back.
    pltpu.make_async_copy(k_in.at[wpage], k_pg, wsems.at[0, 0]).wait()
    pltpu.make_async_copy(v_in.at[wpage], v_pg, wsems.at[0, 1]).wait()
    sel = jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size, 1), 1) == slot
    k_pg[...] = jnp.where(sel, k_new_ref[0][:, None, :], k_pg[...])
    v_pg[...] = jnp.where(sel, v_new_ref[0][:, None, :], v_pg[...])
    pltpu.make_async_copy(k_pg, k_out.at[wpage], wsems.at[1, 0]).start()
    pltpu.make_async_copy(v_pg, v_out.at[wpage], wsems.at[1, 1]).start()
    # The aliased pools must hold the append when this grid step retires.
    pltpu.make_async_copy(k_pg, k_out.at[wpage], wsems.at[1, 0]).wait()
    pltpu.make_async_copy(v_pg, v_out.at[wpage], wsems.at[1, 1]).wait()


def fused_decode_attention_pallas(
        q: jax.Array,                    # [B, n_q, hd]
        k_new: jax.Array,                # [B, n_kv, hd]
        v_new: jax.Array,                # [B, n_kv, hd]
        k_pages: jax.Array,              # [pages, n_kv, ps, hd]
        v_pages: jax.Array,
        page_table: jax.Array,           # [B, max_pages] i32
        context_lens: jax.Array,         # [B] i32, INCLUDING the new token
        interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (attn_out [B, n_q, hd], k_pages, v_pages) with the new
    token's K/V appended in place (pools are donated via aliasing).

    XLLM_PAGE_CHUNK / XLLM_PAGE_PIPELINE are resolved here, OUTSIDE jit,
    and passed static — a shape-keyed cache would silently pin the
    first-traced variant."""
    import os

    return _fused_impl(q, k_new, v_new, k_pages, v_pages, page_table,
                       context_lens,
                       chunk=page_chunk_size(page_table.shape[1]),
                       pipeline_rows=os.environ.get(
                           "XLLM_PAGE_PIPELINE", "") == "row",
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "pipeline_rows",
                                             "interpret"))
def _fused_impl(q, k_new, v_new, k_pages, v_pages, page_table,
                context_lens, *, chunk: int, pipeline_rows: bool = False,
                interpret: bool = False):
    B, n_q, hd = q.shape
    _, n_kv, page_size, _ = k_pages.shape
    max_pages = page_table.shape[1]
    group = n_q // n_kv
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(_kernel, page_size=page_size, n_kv=n_kv,
                               group=group, scale=scale,
                               max_pages=max_pages, chunk=chunk,
                               pipeline_rows=pipeline_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b, pt, cl: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, hd), lambda b, pt, cl: (b, 0, 0)),
            pl.BlockSpec((1, n_kv, hd), lambda b, pt, cl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # k pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # v pool stays in HBM
        ],
        out_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b, pt, cl: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), k_pages.dtype),
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((2, 2)),     # tail-page read/write (k,v)
            pltpu.VMEM((n_kv, page_size, hd), k_pages.dtype),  # k_pg
            pltpu.VMEM((n_kv, page_size, hd), v_pages.dtype),  # v_pg
            pltpu.VMEM((n_q, 128), jnp.float32),   # m
            pltpu.VMEM((n_q, 128), jnp.float32),   # l
            pltpu.VMEM((n_q, hd), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_q, hd), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # Flattened operand order: (page_table, context_lens, q, k_new,
        # v_new, k_pages, v_pages) -> pools at 5/6 alias outputs 1/2.
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(page_table, context_lens, q, k_new, v_new, k_pages, v_pages)
