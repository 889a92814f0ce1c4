"""Shared scaffolding for the paged-attention Pallas kernels (decode,
context-parallel partial):

- `make_chunk_dma`: a 2-slot VMEM ring of `chunk`-page blocks. A full
  chunk whose pool pages are adjacent, ascending or descending, is ONE
  async copy per side (`pool[lowest : lowest + chunk]`), whichever way it
  runs; any other chunk is one copy per page. The chunk's direction comes
  back to the kernel, which takes the token positions of a descending
  chunk's rows in reverse (`chunk_token_offsets`);
- the same rule over a host page-table row is `page_walk.walk_run_counts`
  (what the engine's telemetry counts, and the tests' oracle of which
  path a chunk takes);
- `masked_kv_f32` / `flash_accumulate`: the per-head chunk read and the
  online-softmax (flash) m/l/acc update.

Extracted so a fix to the DMA pattern or the accumulate numerics lands
in every kernel at once."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def make_chunk_dma(page_table_ref, b, n_pages, chunk,
                   k_hbm, v_hbm, k_buf, v_buf, sems):
    """Returns (probe(c), start_chunk(slot, c, run), wait_chunk(slot, c,
    run)). ``probe`` reads chunk c's table entries once and gives
    (d, lowest): d = +1 / -1 where the chunk is a run up / down from its
    first page, 0 where it goes page by page; start and wait take that one
    answer, so the bytes waited for are the bytes started."""
    last = page_table_ref.shape[1] - 1

    def probe(c):
        base = c * chunk
        # Reads past the table are clamped and cannot count: such a chunk
        # is not full.
        first = page_table_ref[b, jnp.minimum(base, last)]
        up = down = base + chunk <= n_pages
        for j in range(1, chunk):
            page = page_table_ref[b, jnp.minimum(base + j, last)]
            up &= page == first + j
            down &= page == first - j
        d = jnp.where(up, 1, jnp.where(down, -1, 0))
        return d, jnp.where(d < 0, first - (chunk - 1), first)

    def each_copy(slot, c, run, act):
        d, lowest = run

        @pl.when(d != 0)
        def _run():
            act(pltpu.make_async_copy(
                k_hbm.at[pl.ds(lowest, chunk)], k_buf.at[slot],
                sems.at[slot, 0]))
            act(pltpu.make_async_copy(
                v_hbm.at[pl.ds(lowest, chunk)], v_buf.at[slot],
                sems.at[slot, 1]))

        @pl.when(d == 0)
        def _pages():
            for j in range(chunk):
                p = c * chunk + j

                @pl.when(p < n_pages)
                def _():
                    page = page_table_ref[b, p]
                    act(pltpu.make_async_copy(
                        k_hbm.at[page], k_buf.at[slot, j],
                        sems.at[slot, 0]))
                    act(pltpu.make_async_copy(
                        v_hbm.at[page], v_buf.at[slot, j],
                        sems.at[slot, 1]))

    def start_chunk(slot, c, run):
        each_copy(slot, c, run, lambda copy: copy.start())

    def wait_chunk(slot, c, run):
        each_copy(slot, c, run, lambda copy: copy.wait())

    return probe, start_chunk, wait_chunk


def chunked_page_walk(page_table_ref, b, n_pages, chunk,
                      k_hbm, v_hbm, k_buf, v_buf, sems, compute, c_lo=0):
    """Run the double-buffered page walk for grid row ``b``, calling
    ``compute(c, slot, d)`` per chunk: chunk c+1 loads while chunk c
    computes; each row pays one cold-start DMA stall. ``d`` is the chunk's
    direction (`make_chunk_dma`): the rows of a chunk with direction -1
    lie in the buffer in reverse page order.

    ``c_lo`` is the FIRST chunk to walk — a sliding-window decode
    (gemma-2 local layers) never needs pages wholly below ctx - window,
    so the walk can start there instead of chunk 0.
    """
    n_chunks = jnp.maximum(pl.cdiv(n_pages, chunk) - c_lo, 0)
    probe, start_chunk, wait_chunk = make_chunk_dma(
        page_table_ref, b, n_pages, chunk, k_hbm, v_hbm, k_buf, v_buf,
        sems)

    @pl.when(n_chunks > 0)
    def _run():
        first = probe(c_lo)
        start_chunk(0, c_lo, first)

        def body(i, run):
            c = c_lo + i
            slot = jax.lax.rem(i, 2)
            # Past the last chunk no chunk is full: nothing is started.
            ahead = probe(c + 1)

            @pl.when(i + 1 < n_chunks)
            def _prefetch():
                start_chunk(1 - slot, c + 1, ahead)

            wait_chunk(slot, c, run)
            compute(c, slot, run[0])
            return ahead

        jax.lax.fori_loop(0, n_chunks, body, first)


def token_offset_maps(chunk: int, page_size: int, shape, dim: int):
    """What `chunk_token_offsets` needs of a chunk's buffer rows, none of
    it depending on the chunk: build it once per kernel body, outside the
    walk. (idx, flip): each row's own index along ``dim`` of an int32
    ``shape`` (span = chunk * page_size long), its token offset where the
    chunk's pages lie in table order; and what a row adds where the chunk
    was fetched downwards, so that page j takes the offsets of page
    chunk-1-j."""
    idx = jax.lax.broadcasted_iota(jnp.int32, shape, dim)
    page_start = idx - jax.lax.rem(idx, page_size)
    return idx, (chunk - 1) * page_size - 2 * page_start


def chunk_token_offsets(maps, d):
    """Each buffer row's token offset inside its chunk, for the walk's
    direction ``d`` (-1: the chunk's pages lie reversed)."""
    idx, flip = maps
    return idx + flip * (d < 0).astype(jnp.int32)


# --------------------------------------------------------------- page movers
#
# Device-side movers for the tiered KV-cache data plane (engine/kv_tier.py):
# gather a hash block's pages out of the pool (offload: the gathered buffer
# is downloaded to the host tier off-thread) and scatter a host-restored
# block back into freshly allocated pages (onload, dispatched ahead of the
# prefill that reads them). On TPU the gather runs as a Pallas kernel — one
# async copy per (layer, k/v, page) row, pure DMA, no compute — so the
# block never stages through VMEM-size-limited compute tiles; elsewhere
# (CPU tests, interpret mode off) a plain XLA gather/scatter is identical.


def _pallas_page_mover_on() -> bool:
    """Pallas DMA mover on real TPU backends; XLA gather/scatter fallback
    elsewhere. XLLM_PALLAS_INTERPRET=1 forces the kernel in interpret
    mode (parity tests on CPU)."""
    import os

    from .attention import _backend, note_path, program_mesh

    if program_mesh() is not None:
        # The pool is sharded and GSPMD cannot partition a Mosaic kernel.
        note_path("page_mover", "xla-gather (pool sharded over a mesh)")
        return False
    on = (os.environ.get("XLLM_PALLAS_INTERPRET", "") == "1"
          or _backend() == "tpu")
    note_path("page_mover", "pallas-dma" if on else "xla-gather")
    return on


def _gather_pages_kernel(ids_ref, pool, out, sem):
    """grid (L, 2, n): one page row per step, pure DMA (ANY→ANY), no
    compute tile — the block never stages through VMEM."""
    li = pl.program_id(0)
    si = pl.program_id(1)
    i = pl.program_id(2)
    cp = pltpu.make_async_copy(pool.at[li, si, ids_ref[i]],
                               out.at[li, si, i], sem)
    cp.start()
    cp.wait()


def _scatter_pages_kernel(ids_ref, blk, pool_in, pool_out, sem):
    """grid (L, 2, n): pool_in aliases pool_out (in-place page writes);
    only the selected page rows move."""
    del pool_in   # aliased with pool_out; pages not written keep their data
    li = pl.program_id(0)
    si = pl.program_id(1)
    i = pl.program_id(2)
    cp = pltpu.make_async_copy(blk.at[li, si, i],
                               pool_out.at[li, si, ids_ref[i]], sem)
    cp.start()
    cp.wait()


def gather_kv_pages(kv, page_ids):
    """kv: [L, 2, num_pages, n_kv, ps, hd]; page_ids: [n] int32 →
    [L, 2, n, n_kv, ps, hd] block buffer (a NEW array; the pool is
    untouched, so the caller can download it off-thread while later
    programs overwrite the pages)."""
    if not _pallas_page_mover_on():
        return kv[:, :, page_ids]
    import os

    L, _, _, n_kv, ps, hd = kv.shape
    n = page_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, 2, n),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        _gather_pages_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((L, 2, n, n_kv, ps, hd), kv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=os.environ.get("XLLM_PALLAS_INTERPRET", "") == "1",
    )(page_ids, kv)


def scatter_kv_pages(kv, page_ids, block):
    """Inverse of :func:`gather_kv_pages`: write `block`
    [L, 2, n, n_kv, ps, hd] into the pool at `page_ids`; returns the
    updated pool (callers donate it through their jit wrapper)."""
    block = block.astype(kv.dtype)
    if not _pallas_page_mover_on():
        return kv.at[:, :, page_ids].set(block)
    import os

    L = kv.shape[0]
    n = page_ids.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(L, 2, n),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        _scatter_pages_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(kv.shape, kv.dtype),
        # Flattened operand order (ids, blk, pool): pool at 2 aliases the
        # output — in-place page writes, no pool copy.
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=os.environ.get("XLLM_PALLAS_INTERPRET", "") == "1",
    )(page_ids, block, kv)


def masked_kv_f32(k_buf, v_buf, slot, kv, pos_col, bound):
    """Read one KV head's chunk from the ring as f32 ``[span, hd]``,
    zeroing V rows at positions >= ``bound``: their probabilities are 0,
    but 0 x garbage from never-DMA'd (or concurrently written) sub-buffers
    must not reach the accumulator (0 x NaN = NaN). Row positions come as
    a column vector ``pos_col: [span, 1]`` (Mosaic cannot transpose 1-bit
    vectors): a chunk's rows need not lie in token order."""
    k = k_buf[slot, :, kv].astype(jnp.float32)
    span = k.shape[0] * k.shape[1]
    k = k.reshape(span, -1)
    v = v_buf[slot, :, kv].astype(jnp.float32).reshape(span, -1)
    return k, jnp.where(pos_col < bound, v, 0.0)


def flash_accumulate(rows, s, v, m_scr, l_scr, acc_scr):
    """Online-softmax update of the (m, l, acc) scratch rows with masked
    scores ``s: [R, span]`` and values ``v: [span, hd]``. Fully-masked
    rows are exact: p is re-zeroed where s is the mask sentinel, so a row
    whose every key is masked in this chunk contributes nothing (without
    the guard, exp(NEG_INF - NEG_INF) = 1 would pollute l/acc)."""
    m_prev = m_scr[rows, :1]
    l_prev = l_scr[rows, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p_ = jnp.exp(s - m_new)
    p_ = jnp.where(s <= NEG_INF / 2, 0.0, p_)
    l_new = l_prev * alpha + jnp.sum(p_, axis=1, keepdims=True)
    acc_scr[rows, :] = acc_scr[rows, :] * alpha + \
        jax.lax.dot_general(p_, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    m_scr[rows, :1] = m_new
    l_scr[rows, :1] = l_new
