"""Context-parallel paged DECODE attention: the KV page pool sharded over
the mesh `seq` axis (SURVEY §5.7 — ring attention covers prefill; this
covers decode once a sequence's context outgrows one device's HBM).

Mechanism: pages are sharded round-robin-by-range across the seq axis
(device d owns pages [d*P/n, (d+1)*P/n)). Each device computes flash
statistics (m, l, acc) for every query over ONLY the pages it owns
(page-table entries outside its range are masked), then the per-device
partials merge with a log-sum-exp reduction over the axis:

    m_g   = pmax(m)
    l_g   = psum(l * exp(m - m_g))
    acc_g = psum(acc * exp(m - m_g))
    out   = acc_g / l_g

One psum pair over ICI per decode step — no device ever materializes
another shard's pages.

Two per-shard bodies, selected by the shared Mosaic gate:
- Pallas partial kernel (accelerators): each shard compacts its owned
  page-table entries to the front and walks ONLY those pages with the
  chunked double-buffered page DMA shared with the decode kernel
  (ops/pallas_page_dma.py) — per-step HBM traffic is the occupied,
  locally-owned pages, nothing else, and it returns raw (m, l, acc) for
  the cross-shard merge.
- Dense XLA fallback (CPU tests / non-Mosaic shapes): gathers the local
  page span to a dense tensor per step — correctness-first (this was the
  only body in round 2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from .page_walk import page_chunk_size
from .pallas_page_dma import (
    NEG_INF,
    attend_chunk,
    chunked_page_walk,
    kv_word_rows,
    v_word_mask,
)

_NEG_INF = NEG_INF


def _local_partial(q, k_pages, v_pages, page_table, context_lens,
                   axis_name: str, scale):
    """Per-device body. k/v_pages: the LOCAL page shard
    [P_loc, n_kv, ps, hd]; page ids in page_table are global."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    P_loc = k_pages.shape[0]
    lo = my * P_loc

    B, H, hd = q.shape
    n_kv = k_pages.shape[1]
    ps = k_pages.shape[2]
    n_rep = H // n_kv
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    # Local gather: clamp global ids into the local shard; out-of-range
    # entries keep index 0 and are masked out of the softmax.
    local_idx = page_table - lo                         # [B, max_pages]
    owned = (local_idx >= 0) & (local_idx < P_loc)
    safe_idx = jnp.where(owned, local_idx, 0)
    g = k_pages[safe_idx]                               # [B, mp, n_kv, ps, hd]
    gv = v_pages[safe_idx]
    mp = safe_idx.shape[1]
    k = g.transpose(0, 1, 3, 2, 4).reshape(B, mp * ps, n_kv, hd)
    v = gv.transpose(0, 1, 3, 2, 4).reshape(B, mp * ps, n_kv, hd)
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)

    qf = q.astype(jnp.float32) * scale
    scores = jnp.einsum("bhd,bkhd->bhk", qf, k.astype(jnp.float32))
    pos = jnp.arange(mp * ps)[None, :]
    valid = (pos < context_lens[:, None]) & \
        jnp.repeat(owned, ps, axis=1)                   # [B, mp*ps]
    scores = jnp.where(valid[:, None, :], scores, _NEG_INF)

    m = jnp.max(scores, axis=-1, keepdims=True)          # [B, H, 1]
    p = jnp.exp(scores - m)
    p = jnp.where(scores <= _NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))

    # Merge flash stats across the seq axis.
    m_g = jax.lax.pmax(m, axis_name)
    w = jnp.exp(jnp.where(m <= _NEG_INF / 2, _NEG_INF, m) - m_g)
    w = jnp.where(m <= _NEG_INF / 2, 0.0, w)
    l_g = jax.lax.psum(l * w, axis_name)
    acc_g = jax.lax.psum(acc * w[..., 0][..., None], axis_name)
    out = acc_g / jnp.maximum(l_g[..., 0][..., None], 1e-9)
    return out.astype(q.dtype)


def _partial_kernel(local_pt_ref, starts_ref, n_local_ref, clens_ref,
                    q_ref,                       # VMEM block [1, n_q, hd]
                    k_hbm, v_hbm,                # LOCAL pool shard in HBM
                    m_out, l_out, acc_out,
                    k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                    *, page_size: int, group: int, scale: float,
                    max_pages: int, chunk: int):
    """Flash partial stats over this shard's owned pages only.

    local_pt_ref: [B, mp] LOCAL page indices, owned entries compacted to
    the front (n_local_ref[b] of them); starts_ref: [B, mp] each entry's
    global token start (ctx for non-owned → fully masked)."""
    b = pl.program_id(0)
    ctx = clens_ref[b]
    n_pages = jnp.minimum(n_local_ref[b], max_pages)

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def compute(c, slot, d):
        # Per-row global token positions: compacted pages are not
        # contiguous, so each page contributes start_j + iota(ps). A chunk
        # the walk fetched downwards holds its entries in reverse.
        # Built once per orientation from an iota and `chunk` scalar
        # selects: Mosaic has no layout for reshaping a [chunk, ps] i32
        # tile into [1, span] / [span, 1].
        base = c * chunk
        span = chunk * page_size
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        # The column form is per 32-bit word of V (`v_word_mask`): the
        # position of the first of the rows a word holds.
        word_rows = kv_word_rows(v_buf.dtype)
        sub = word_rows * jax.lax.broadcasted_iota(
            jnp.int32, (span // word_rows, 1), 0)
        pos_row = jnp.full((1, span), ctx, jnp.int32)
        pos_col = jnp.full((span // word_rows, 1), ctx, jnp.int32)
        for j in range(chunk):
            entry = base + jnp.where(d < 0, chunk - 1 - j, j)
            # Chunk-padding entries (entry >= n_pages) were never
            # DMA'd — their buffer rows are stale. Position them at
            # ctx so both masks reject them (clamping the table read
            # instead would alias a REAL page's positions and let
            # stale K/V through).
            st = jnp.where(
                entry < n_pages,
                starts_ref[b, jnp.minimum(entry, max_pages - 1)],
                ctx)
            lo, hi = j * page_size, (j + 1) * page_size
            pos_row = jnp.where((lane >= lo) & (lane < hi),
                                st + lane - lo, pos_row)
            pos_col = jnp.where((sub >= lo) & (sub < hi),
                                st + sub - lo, pos_col)
        mask = pos_row < ctx
        attend_chunk(q_ref[0], group, k_buf, v_buf, slot,
                     v_word_mask(pos_col, ctx, word_rows), scale,
                     lambda s: jnp.where(mask, s, _NEG_INF),
                     m_scr, l_scr, acc_scr)

    chunked_page_walk(local_pt_ref, b, n_pages, chunk, k_hbm, v_hbm,
                      k_buf, v_buf, sems, compute)

    m_out[0] = m_scr[...]
    l_out[0] = l_scr[...]
    acc_out[0] = acc_scr[...]


def _paged_partial_pallas(q, k_pages, v_pages, local_pt, starts, n_local,
                          context_lens, scale: float,
                          interpret: bool = False):
    """Per-shard raw flash stats: returns (m [B, n_q, 128],
    l [B, n_q, 128], acc [B, n_q, hd]) — only column 0 of m/l is live."""
    return _paged_partial_impl(q, k_pages, v_pages, local_pt, starts,
                               n_local, context_lens, scale=scale,
                               chunk=page_chunk_size(local_pt.shape[1]),
                               interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("scale", "chunk", "interpret"))
def _paged_partial_impl(q, k_pages, v_pages, local_pt, starts, n_local,
                        context_lens, *, scale: float, chunk: int,
                        interpret: bool = False):
    B, n_q, hd = q.shape
    _, n_kv, page_size, _ = k_pages.shape
    max_pages = local_pt.shape[1]
    group = n_q // n_kv
    kernel = functools.partial(_partial_kernel, page_size=page_size,
                               group=group, scale=scale,
                               max_pages=max_pages, chunk=chunk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, n_q, hd), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # local k shard in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # local v shard in HBM
        ],
        out_specs=[
            pl.BlockSpec((1, n_q, 128), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_q, 128), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((1, n_q, hd), lambda b, *_: (b, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), k_pages.dtype),
            pltpu.VMEM((2, chunk, n_kv, page_size, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_q, 128), jnp.float32),   # m
            pltpu.VMEM((n_q, 128), jnp.float32),   # l
            pltpu.VMEM((n_q, hd), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_q, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, n_q, 128), jnp.float32),
            jax.ShapeDtypeStruct((B, n_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(local_pt, starts, n_local, context_lens, q, k_pages, v_pages)


def _local_partial_kernelized(q, k_pages, v_pages, page_table,
                              context_lens, axis_name: str, scale,
                              interpret: bool):
    """Pallas per-shard body: compact owned page-table entries, walk only
    those pages (chunked double-buffered DMA), merge raw stats over the
    seq axis."""
    my = jax.lax.axis_index(axis_name)
    P_loc = k_pages.shape[0]
    lo = my * P_loc
    ps = k_pages.shape[2]
    hd = q.shape[-1]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    local_idx = page_table - lo                          # [B, mp]
    owned = (local_idx >= 0) & (local_idx < P_loc)
    # Walk only the OCCUPIED span (cdiv(ctx, ps) entries), matching the
    # single-device decode kernel: the table tail is garbage-page padding
    # (id 0 — which would otherwise count as "owned" on shard 0 and be
    # DMA'd every step just to be masked out).
    mp = page_table.shape[1]
    owned &= (jnp.arange(mp, dtype=jnp.int32)[None, :] * ps
              < context_lens[:, None])
    # Stable sort brings owned entries to the front in table order.
    order = jnp.argsort(~owned, axis=1, stable=True)     # [B, mp]
    local_pt = jnp.take_along_axis(
        jnp.where(owned, local_idx, 0), order, axis=1).astype(jnp.int32)
    # Each entry's global token start; non-owned → ctx (fully masked and
    # never DMA'd — they sit past n_local).
    starts = jnp.where(jnp.take_along_axis(owned, order, axis=1),
                       order * ps, context_lens[:, None]).astype(jnp.int32)
    n_local = owned.sum(axis=1).astype(jnp.int32)

    m, l, acc = _paged_partial_pallas(q, k_pages, v_pages, local_pt,
                                      starts, n_local, context_lens,
                                      scale=float(scale),
                                      interpret=interpret)
    m = m[..., :1]                                       # live column
    l = l[..., :1]
    m_g = jax.lax.pmax(m, axis_name)
    w = jnp.exp(jnp.where(m <= _NEG_INF / 2, _NEG_INF, m) - m_g)
    w = jnp.where(m <= _NEG_INF / 2, 0.0, w)
    l_g = jax.lax.psum(l * w, axis_name)
    acc_g = jax.lax.psum(acc * w, axis_name)
    out = acc_g / jnp.maximum(l_g, 1e-9)
    return out.astype(q.dtype)


def cp_paged_attention(q: jax.Array, k_pages: jax.Array,
                       v_pages: jax.Array, page_table: jax.Array,
                       context_lens: jax.Array, mesh: Mesh,
                       seq_axis: str = "seq",
                       scale: float | None = None) -> jax.Array:
    """q: [B, n_heads, hd]; k/v_pages: [num_pages, n_kv, ps, hd] sharded
    (or shardable) on the page axis over `seq_axis`; num_pages must divide
    by the axis size. Returns [B, n_heads, hd], identical to
    single-device paged attention (parity-tested)."""
    from .attention import _backend, _pallas_interpret, attention_path

    # The dispatcher's eligibility rule (it records the path).
    kernel_ok = attention_path(
        _backend(), _pallas_interpret(), q.shape[-1], q.shape[-2],
        k_pages.shape[1], q.dtype, context_parallel=True
    ).startswith("cp-pallas")
    if kernel_ok:
        body = functools.partial(_local_partial_kernelized,
                                 axis_name=seq_axis, scale=scale,
                                 interpret=_pallas_interpret())
    else:
        body = functools.partial(_local_partial, axis_name=seq_axis,
                                 scale=scale)
    # pallas_call's out_shape carries no varying-mesh-axes metadata,
    # which trips shard_map's vma check on the kernel body.
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(seq_axis), P(seq_axis), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q, k_pages, v_pages, page_table, context_lens)
