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
- `attend_chunk`: one chunk's online-softmax (flash) m/l/acc update of
  every KV head. `k_operand` / `v_operand` hand a head's K and V to the
  MXU in the type the pool holds them (bfloat16 stays bfloat16: no
  float32 copy), `v_word_mask` is the guard over V's rows past the
  context, `exact_rows` keeps the probabilities' 24 bits as three
  bfloat16 terms.

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


def token_offset_maps(chunk: int, page_size: int, shape, dim: int,
                      step: int = 1):
    """What `chunk_token_offsets` needs of a chunk's buffer rows, none of
    it depending on the chunk: build it once per kernel body, outside the
    walk. (idx, flip): the index of every ``step``-th buffer row along
    ``dim`` of an int32 ``shape`` (span / step long), its token offset
    where the chunk's pages lie in table order; and what a row adds where
    the chunk was fetched downwards, so that page j takes the offsets of
    page chunk-1-j."""
    idx = step * jax.lax.broadcasted_iota(jnp.int32, shape, dim)
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


def kv_word_rows(dtype) -> int:
    """Token rows that share one 32-bit word of a ring buffer of ``dtype``
    (consecutive rows, the first in the low bits): 2 for bfloat16."""
    return 4 // jnp.dtype(dtype).itemsize


def v_word_mask(first_pos, bound, rows: int):
    """The guard over V as a mask of its 32-bit words: all ones over each
    row at a position < ``bound``, zeros over the others. Those have
    probability 0, but 0 x garbage from never-DMA'd (or concurrently
    written) sub-buffers must not reach the accumulator (0 x NaN = NaN),
    and an AND clears any bit pattern. ``first_pos: [span / rows, 1]`` is
    the position of each word's first row, as a column (Mosaic cannot
    transpose 1-bit vectors): a chunk's rows need not lie in token
    order, the ``rows`` of a word do."""
    bits = 32 // rows
    keep = jnp.zeros(first_pos.shape, jnp.uint32)
    for r in range(rows):
        field = jnp.uint32(((1 << bits) - 1) << (bits * r))
        keep |= jnp.where(first_pos + r < bound, field, jnp.uint32(0))
    return keep


def _kv_words(buf, slot, kv):
    w = buf.bitcast(jnp.uint32)[slot, :, kv]
    return w.reshape(w.shape[0] * w.shape[1], -1)


def k_operand(k_buf, slot, kv):
    """One KV head's keys of the chunk in ring slot ``slot`` as the score
    matmul's ``[span, hd]`` operand, in the type the pool holds them. K
    and V never exist in float32 here: a bfloat16 x bfloat16 product is
    exact in float32, so a float32 copy of either adds no bit. They are
    read as the buffer's 32-bit words, whose vregs are the packed
    (16, 128) tiles the MXU takes (read as bfloat16 a buffer row comes
    half a vreg at a time and is shuffled into those tiles, which costs
    what the float32 copies did)."""
    return pltpu.bitcast(_kv_words(k_buf, slot, kv), k_buf.dtype)


def v_operand(v_buf, slot, kv, v_keep):
    """The head's values, as `k_operand` its keys; the words pass
    ``v_keep`` (`v_word_mask`) on the way."""
    return pltpu.bitcast(_kv_words(v_buf, slot, kv) & v_keep, v_buf.dtype)


def exact_rows(x, dtype):
    """``x: [R, n]`` as rows of ``dtype`` that sum to it exactly, and how
    many terms they are: ``x`` itself for float32, or where it is of that
    type already; float32 ``x`` for bfloat16 is three terms (hi, mid, lo:
    3 x 8 significant bits carry all 24) stacked on the row side
    ``[3R, n]``, so ONE product against a bfloat16 operand is the float32
    product to the last bit of each term, and the extra rows ride on the
    few-row side, not on the other side's tiles. Fewer terms would round
    ``x``: a precision change."""
    if dtype != jnp.bfloat16 or x.dtype == dtype:
        return x.astype(dtype), 1
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    rest = x - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.concatenate([hi, mid, rest - mid]).astype(jnp.bfloat16), 3


def sum_terms(y, terms: int):
    """Undo `exact_rows`' stacking on a product's rows: ``[terms * R, n]``
    -> ``[R, n]``, summed in float32."""
    rows = y.shape[0] // terms
    out = y[:rows]
    for t in range(1, terms):
        out = out + y[t * rows:(t + 1) * rows]
    return out


def head_scores(q, k, scale: float):
    """Scores ``[G, span]`` of one KV head's query rows ``q: [G, hd]``
    against its chunk's keys ``k: [span, hd]`` (`k_operand`), float32
    accumulation. ``scale`` multiplies the float32 scores, not ``q``
    before the product: a bfloat16 ``q`` then meets bfloat16 keys as the
    numbers both are, and every product is exact."""
    q_rows, terms = exact_rows(q, k.dtype)
    s = jax.lax.dot_general(q_rows, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return sum_terms(s, terms) * scale


def flash_softmax(rows, s, m_scr, l_scr):
    """The online-softmax half of a flash update: moves the (m, l) scratch
    rows on by masked scores ``s: [R, span]`` and returns (p, alpha), the
    chunk's unnormalised probabilities and the factor the old accumulator
    is worth under the new maximum. Fully-masked rows are exact: p is
    re-zeroed where s is the mask sentinel, so a row whose every key is
    masked in this chunk contributes nothing (without the guard,
    exp(NEG_INF - NEG_INF) = 1 would pollute l/acc)."""
    m_prev = m_scr[rows, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    l_scr[rows, :1] = l_scr[rows, :1] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
    m_scr[rows, :1] = m_new
    return p, alpha


def flash_values(rows, p, alpha, v, acc_scr):
    """The other half: ``acc = acc * alpha + p . v`` on the scratch rows,
    ``v: [span, hd]`` as `v_operand` gives it; the probabilities meet it
    through `exact_rows`, so the product is float32's whatever V's
    type."""
    p_rows, terms = exact_rows(p, v.dtype)
    pv = jax.lax.dot_general(p_rows, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_scr[rows, :] = acc_scr[rows, :] * alpha + sum_terms(pv, terms)


def attend_chunk(q, group: int, k_buf, v_buf, slot, v_keep, scale: float,
                 finish_scores, m_scr, l_scr, acc_scr):
    """One chunk's flash update of every KV head: ``q: [n_q, hd]`` in
    groups of ``group`` rows against the chunk in ring slot ``slot``;
    ``finish_scores`` takes a head's scaled scores ``[G, span]`` to the
    masked ones (`NEG_INF` where a key is not visible).

    Three passes over the heads, not one pass of three steps. A head's
    p . V waits for its softmax, and that for its q . K^T to come back
    from the MXU and through two cross-lane reductions: head after head,
    the MXU idles through every one of those waits. Pass by pass, the
    heads' products stand behind one another with nothing to wait for."""
    heads = [slice(kv * group, (kv + 1) * group)
             for kv in range(k_buf.shape[2])]
    scores = [finish_scores(head_scores(q[rows], k_operand(k_buf, slot, kv),
                                        scale))
              for kv, rows in enumerate(heads)]
    probs = [flash_softmax(rows, s, m_scr, l_scr)
             for rows, s in zip(heads, scores)]
    for kv, (rows, (p, alpha)) in enumerate(zip(heads, probs)):
        flash_values(rows, p, alpha, v_operand(v_buf, slot, kv, v_keep),
                     acc_scr)
