"""Attention primitives for the paged-KV engine.

Layouts:
- KV pool: ``pool: [L, 2, num_pages, n_kv, page_size, hd]`` (K at index 0
  of the second dim, V at 1): ONE donated buffer. Writers scatter into it
  in place and readers take ``(pool, layer)``; no forward slices a layer
  out of it into a temporary or stacks one back (a Pallas operand needs a
  buffer of its own, so a sliced layer is a copy of that layer — once per
  layer that is the whole pool, every step). ``k_pages/v_pages:
  [num_pages, n_kv, page_size, hd]`` name one layer's views where an XLA
  gather reads them. The (page_size, head_dim) minor dims match the bf16
  (16, 128) TPU tile so the Pallas decode kernel reads whole pages as
  aligned blocks.
- ``page_tables: [B, max_pages]`` int32 — page ids per sequence, in order.
- ``context_lens: [B]`` int32 — tokens currently in cache per sequence.

Numerics: matmuls in model dtype (bf16 on TPU), softmax in f32.

The XLA paths below are the portable implementation (they run on CPU test
meshes and compile well on TPU); `ops/pallas_paged_attention.py` (decode)
and `ops/pallas_prefill_attention.py` (prefill) provide the hand-written
TPU kernels and the dispatchers select per backend and shape.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import threading
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import AXIS_MODEL, AXIS_SEQ
from ..utils import get_logger

logger = get_logger(__name__)

_NEG_INF = -1e30


def _backend() -> str:
    """The backend the dispatch gates below key on. One hook so the
    described-chip compile tests can steer every gate to the TPU branch
    while `jax.default_backend()` still reads "cpu"."""
    return jax.default_backend()


# Trace-time path record. The kernel-or-XLA choice below happens while a
# program is TRACED and is invisible afterwards, so every dispatcher
# notes which path it took: `{program: {op: path}}`, logged once per
# distinct entry. The engine traces each of its programs under
# `trace_program(label, record=engine dict, mesh)` and returns that dict
# from `stats()`; calls outside any program land in `PATH_RECORD[""]`.
PATH_RECORD: dict[str, dict[str, str]] = {}


class _Program(NamedTuple):
    label: str = ""
    record: dict = PATH_RECORD
    mesh: Any = None
    ring: bool = False


_prog_ctx = threading.local()


@contextlib.contextmanager
def trace_program(label: str, record: dict | None = None, mesh=None,
                  ring: bool = False):
    """Trace context for one jitted program, and everything the
    dispatchers below know about it besides their arguments: its name in
    the path record and its `mesh`. A model axis > 1 makes
    `paged_attention` run its Pallas kernel per head-shard under
    `shard_map` (Mosaic kernels cannot be partitioned by GSPMD); a seq
    axis > 1 means the KV pool is sharded over it, so `paged_attention`
    goes through the flash-stats-merge context-parallel op. `ring`
    (SURVEY.md §5.7): `prefill_attention` runs the suffix self-attention
    as the blockwise ring over the seq axis — the engine traces that
    variant as its own program and sends it prefix-free prompts only
    (prefix attention would need a traced branch, which XLA cannot take
    on a dynamic prefix_lens)."""
    prev = getattr(_prog_ctx, "cfg", None)
    _prog_ctx.cfg = _Program(
        label, PATH_RECORD if record is None else record, mesh, ring)
    try:
        yield
    finally:
        _prog_ctx.cfg = prev


def _program() -> _Program:
    return getattr(_prog_ctx, "cfg", None) or _Program()


def note_path(op: str, path: str) -> None:
    prog = _program()
    paths = prog.record.setdefault(prog.label, {})
    if paths.get(op) == path:
        return
    paths[op] = path
    # An accelerator program that leaves the kernel must say so loudly.
    loud = _backend() != "cpu" and path.startswith("xla")
    logger.log(logging.WARNING if loud else logging.INFO,
               "attention path: program=%s op=%s -> %s",
               prog.label or "-", op, path)


def program_mesh():
    """The mesh of the program being traced (None outside one, or for a
    single-device program)."""
    mesh = _program().mesh
    return mesh if mesh is not None and mesh.size > 1 else None


def _axis_mesh(axis: str):
    """(mesh, n) of the program being traced when its `axis` is sharded
    n > 1 ways, else (None, 1)."""
    mesh = program_mesh()
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return None, 1
    return mesh, int(mesh.shape[axis])


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dtype)


def rope_cos_sin(positions: jax.Array, head_dim: int,
                 theta: float) -> tuple[jax.Array, jax.Array]:
    """positions [...] -> cos/sin [..., head_dim//2] in f32."""
    inv_freq = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_section: tuple[int, ...] = ()) -> jax.Array:
    """x: [..., n_heads, head_dim]; positions broadcastable to x.shape[:-2].

    With `mrope_section` (Qwen2-VL M-RoPE; half-dim units summing to
    head_dim/2, reference `rope_scaling.mrope_section`), positions may
    instead carry a trailing multimodal axis [..., 3] = (temporal, h, w):
    each half-dim frequency then rotates by ITS section's position
    stream. 1D positions (all axes equal — any text-only sequence, and
    every decode step) take the standard path, which is numerically
    identical for them.
    """
    hd = x.shape[-1]
    if (mrope_section and positions.ndim == x.ndim - 1
            and positions.shape[-1] == len(mrope_section)):
        cos3, sin3 = rope_cos_sin(positions, hd, theta)  # [..., 3, hd/2]
        lo = 0
        cos_parts, sin_parts = [], []
        for k, n in enumerate(mrope_section):
            cos_parts.append(cos3[..., k, lo:lo + n])
            sin_parts.append(sin3[..., k, lo:lo + n])
            lo += n
        cos = jnp.concatenate(cos_parts, axis=-1)        # [..., hd/2]
        sin = jnp.concatenate(sin_parts, axis=-1)
    else:
        cos, sin = rope_cos_sin(positions, hd, theta)    # [..., hd/2]
    cos = cos[..., None, :]                              # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _repeat_kv(kv: jax.Array, n_rep: int) -> jax.Array:
    """GQA: repeat kv heads to match query heads. kv [..., n_kv, hd]."""
    if n_rep == 1:
        return kv
    return jnp.repeat(kv, n_rep, axis=-2)


# ------------------------------------------------------ pool reads / writes
# One gather and one scatter per layer move K and V pages together: their
# rows are pool coordinates (layer, k|v, page), their window one page
# [n_kv, page_size, hd], the pool's contiguous trailing block. `lax`
# directly: a forward traces these once per layer, and jnp's index
# normalisation costs more to trace than the rest of `write_kv`.
_PAGE_GATHER = jax.lax.GatherDimensionNumbers(
    offset_dims=(1, 2, 3), collapsed_slice_dims=(0, 1, 2),
    start_index_map=(0, 1, 2))
_PAGE_SCATTER = jax.lax.ScatterDimensionNumbers(
    update_window_dims=(1, 2, 3), inserted_window_dims=(0, 1, 2),
    scatter_dims_to_operand_dims=(0, 1, 2))


def _page_rows(layer: int, page_ids: jax.Array) -> jax.Array:
    """[n] page ids -> [2n, 3] pool coordinates, K rows then V rows."""
    n = page_ids.shape[0]
    side = jnp.repeat(jnp.arange(2, dtype=jnp.int32), n)
    return jnp.stack([jnp.full((2 * n,), layer, jnp.int32), side,
                      jnp.tile(page_ids.astype(jnp.int32), 2)], axis=1)


def _read_pages(pool: jax.Array, layer: int,
                page_ids: jax.Array) -> jax.Array:
    """K and V pages of `layer`: [n] ids -> [2, n, n_kv, ps, hd], in ONE
    gather on the pool (`pool[layer, side][ids]` would slice the layer
    into a temporary first)."""
    pages = jax.lax.gather(pool, _page_rows(layer, page_ids), _PAGE_GATHER,
                           (1, 1, 1) + pool.shape[3:], mode="clip")
    return pages.reshape(2, -1, *pool.shape[3:])


def write_kv(pool: jax.Array, layer: int, k: jax.Array, v: jax.Array,
             page_table: jax.Array, start: jax.Array,
             lens: jax.Array) -> jax.Array:
    """Write a run of tokens' K/V per sequence into layer `layer` of the
    pool, in place (the pool is donated through every program): the one
    writer of prefill suffixes, verify blocks, prefill chunks and the
    decode token (S = 1).

    k/v: [B, S, n_kv, hd] — token j of row b lands at absolute position
    start[b] + j for j < lens[b]; the rest is bucket padding and is not
    written.

    Whole pages move, not token rows: the run's pages are gathered, the new
    tokens spliced in and the pages scattered back. A page is the pool's
    contiguous [n_kv, page_size, hd] block, so the scatter's window is the
    trailing dims and it updates the pool in its own layout; a scatter of
    [n_kv, hd] token rows at (page, :, slot, :) makes the TPU compiler
    rewrite the whole pool into a slot-major layout and back around it.
    Safe because a partially filled page is private to its sequence
    (`KVPageManager` donates whole hash blocks of whole pages only) and
    the cells outside the run are written back as read. Pages with
    nothing to write (padding, rows past their table) get an index past
    the pool, which the scatter drops.
    """
    B, S, n_kv, hd = k.shape
    ps = pool.shape[4]
    max_pages = page_table.shape[1]
    n_pg = (S + ps - 2) // ps + 1       # pages S consecutive tokens touch
    first = start // ps
    off = start - first * ps                                  # [B], < ps
    slot_ids = first[:, None] + jnp.arange(n_pg)[None, :]     # [B, n_pg]
    # Token index of every (page, slot) cell of the run's pages.
    t = jnp.arange(n_pg * ps)[None, :] - off[:, None]         # [B, n_pg*ps]
    live = ((t >= 0) & (t < lens[:, None])).reshape(B, n_pg, ps)
    page_ids = jnp.take_along_axis(
        page_table, jnp.clip(slot_ids, 0, max_pages - 1), axis=1)
    in_table = (slot_ids >= 0) & (slot_ids < max_pages)
    page_ids = jnp.where(live.any(-1) & in_table, page_ids,
                         pool.shape[2]).reshape(-1)

    kv = jnp.stack([k, v]).astype(pool.dtype)             # [2, B, S, n_kv, hd]
    if S == 1:
        cells = kv[:, :, :, :, None, :]         # the select keeps one slot
    else:
        # Token j of row b at cell off[b] + j of the row's pages.
        kv = jnp.pad(kv, ((0, 0), (0, 0), (ps, n_pg * ps - S), (0, 0),
                          (0, 0)))
        kv = jax.vmap(lambda x, o: jax.lax.dynamic_slice_in_dim(
            x, ps - o, n_pg * ps, axis=1), in_axes=(1, 0), out_axes=1)(
                kv, off)
        cells = kv.reshape(2, B, n_pg, ps, n_kv, hd).transpose(
            0, 1, 2, 4, 3, 5)
    old = _read_pages(pool, layer, page_ids).reshape(
        2, B, n_pg, n_kv, ps, hd)
    new = jnp.where(live[None, :, :, None, :, None], cells, old)
    return jax.lax.scatter(pool, _page_rows(layer, page_ids),
                           new.reshape(-1, n_kv, ps, hd), _PAGE_SCATTER,
                           mode="drop")


# ----------------------------------------------------------- prefill attn
def gather_pages(pool: jax.Array, layer: int,
                 page_table: jax.Array) -> tuple[jax.Array, jax.Array]:
    """K and V of `layer` under a page table: [L, 2, num_pages, n_kv, ps,
    hd] x [B, max_pages] -> two [B, max_pages*ps, n_kv, hd]."""
    B, mp = page_table.shape
    n_kv, ps, hd = pool.shape[3:]
    g = _read_pages(pool, layer, page_table.reshape(-1))
    g = g.reshape(2, B, mp, n_kv, ps, hd).transpose(0, 1, 2, 4, 3, 5)
    g = g.reshape(2, B, mp * ps, n_kv, hd)
    return g[0], g[1]


def prefill_attention_path(backend: str, interpret: bool, S: int,
                           head_dim: int, n_heads: int, n_kv: int, dtype,
                           tp: int = 1, pool: bool = True,
                           ring: bool = False,
                           seq_sharded: bool = False) -> str:
    """The path `prefill_attention` takes and the string
    `/stats`.attention_paths records for it: `ring`, `pallas` (the tiled
    kernel over the pool's pages, `ops/pallas_prefill_attention.py`), or
    `xla-dense (<why>)`, the plain form.

    The kernel's eligibility is the decode kernel's (`attention_path`:
    lane-multiple head dim, integer group, bf16/f32, TPU or interpret
    mode, head counts that divide over `tp`) and a query tile for the
    bucket (`page_walk.prefill_query_tile`). It reads every key from the
    pool, so a call without one (the embeddings path) keeps the plain
    form, as does a pool sharded over the seq axis. Soft cap and window
    ride the kernel as static parameters, as they do in decode."""
    if ring:
        return "ring"
    if not pool:
        return "xla-dense (no pool: the embeddings path)"
    if seq_sharded:
        return f"xla-dense (pool sharded over {AXIS_SEQ})"
    path = attention_path(backend, interpret, head_dim, n_heads, n_kv,
                          dtype, tp)
    if path.startswith("xla"):
        return "xla-dense" + path[len("xla"):]
    from .page_walk import prefill_query_tile

    if not prefill_query_tile(S, n_heads // tp, head_dim,
                              jnp.dtype(dtype).itemsize):
        return (f"xla-dense (shape outside the kernel's tiling: no query "
                f"tile for S={S} heads={n_heads // tp} hd={head_dim})")
    return path


def _on_head_shards(kernel, mesh, heads: P, n_replicated: int):
    """`kernel(q, pool, *replicated)` as it runs under the program's mesh.
    Tensor parallel: GSPMD cannot partition a Mosaic kernel, so each
    device runs it on its own heads: q (spec `heads`) and the pool are
    head-sharded over `model` (KV_PAGES_SPEC), the layer id, the page
    table and the lengths replicated. GQA groups stay whole because both
    head counts divide by tp. pallas_call outputs carry no varying-axes
    metadata, hence check_vma=False."""
    if mesh is None:
        return kernel
    pool_spec = P(None, None, None, AXIS_MODEL, None, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(heads, pool_spec) + (P(),) * n_replicated,
        out_specs=heads, check_vma=False)


def prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      pool: jax.Array | None, layer: int | None,
                      page_table: jax.Array | None,
                      prefix_lens: jax.Array, seq_lens: jax.Array,
                      scale: float | None = None,
                      softcap: float = 0.0, window: int = 0) -> jax.Array:
    """Causal attention for a (possibly prefix-cached) prefill chunk.

    q/k/v: [B, S, n(_kv), hd] for the *suffix* being prefilled; queries also
    attend to the cached prefix (first prefix_lens[b] tokens) read from
    layer `layer` of the paged pool (`pool` None: no cache at all, the
    embeddings path), into which the caller has ALREADY written the
    suffix's k and v (`write_kv`): the kernel reads every key from there.
    seq_lens[b] = valid suffix length (padding masked out; the padding
    rows' outputs are unspecified and unused).
    Returns [B, S, n_heads, hd].

    softcap > 0 tanh-caps the attention scores; window > 0 restricts each
    query to the trailing `window` key positions (gemma-2 local layers).
    The ring does not implement them. Which form runs is
    `prefill_attention_path`'s word: on `pallas` ONE kernel call, whatever
    the prefix; the XLA form below is the plain one (the CPU backend, no
    pool, shapes outside the kernel's tiling).
    """
    B, S, n_heads, hd = q.shape
    n_kv = k.shape[2]
    n_rep = n_heads // n_kv

    if scale is None:
        scale = 1.0 / (hd ** 0.5)

    prog = _program()
    if prog.ring and (softcap != 0.0 or window != 0):
        raise NotImplementedError(
            "ring attention does not support attn softcap/sliding window; "
            "the engine must not enable sequence-parallel prefill for "
            "gemma-2-style models")
    mesh, tp = _axis_mesh(AXIS_MODEL)
    path = prefill_attention_path(
        _backend(), _pallas_interpret(), S, hd, n_heads, n_kv, q.dtype, tp,
        pool=pool is not None, ring=prog.ring,
        seq_sharded=_axis_mesh(AXIS_SEQ)[0] is not None)
    note_path("prefill_attention", path)
    if prog.ring:
        # Context-parallel path: ring attention over the seq mesh axis.
        # Queries past seq_lens are end-padding; causal masking keeps them
        # out of every valid query's window and the engine discards their
        # outputs, so the pure-causal ring is exact here. K/V go in at
        # their GQA head count — the ring repeats them only at use, so the
        # ppermute traffic stays n_rep times smaller.
        from .ring_attention import ring_attention

        return ring_attention(q, k, v, prog.mesh, seq_axis=AXIS_SEQ,
                              scale=scale)
    if path.startswith("pallas"):
        from .pallas_prefill_attention import prefill_attention_pallas

        kernel = functools.partial(
            prefill_attention_pallas, interpret=_pallas_interpret(),
            scale=scale, softcap=softcap, window=window)
        return _on_head_shards(
            kernel, mesh, P(None, None, AXIS_MODEL, None), 4)(
                q, pool, jnp.full((1,), layer, jnp.int32), page_table,
                prefix_lens, seq_lens)

    kf = _repeat_kv(k, n_rep).astype(jnp.float32)
    vf = _repeat_kv(v, n_rep).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale

    def cap(s):
        return softcap * jnp.tanh(s / softcap) if softcap > 0 else s

    # Suffix-suffix scores, causal + padding mask. Absolute positions:
    # query row r sits at prefix_lens[b] + r, key col c at prefix_lens[b]
    # + c — their distance is r - c, so the sliding-window mask here is
    # prefix-independent.
    ss = cap(jnp.einsum("bqhd,bkhd->bhqk", qf, kf))
    rows = jnp.arange(S)[None, :, None]
    cols = jnp.arange(S)[None, None, :]
    mask = (cols <= rows) & (cols < seq_lens[:, None, None])
    if window > 0:
        mask = mask & (rows - cols < window)
    ss = jnp.where(mask[:, None, :, :], ss, _NEG_INF)

    def _suffix_only(_):
        probs = jax.nn.softmax(ss, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, vf)

    if pool is None:
        return _suffix_only(None).astype(q.dtype)

    def _attend_prefix(pt_prefix):
        pk, pv = (_repeat_kv(g, n_rep).astype(jnp.float32)
                  for g in gather_pages(pool, layer, pt_prefix))
        T = pk.shape[1]
        ps_scores = cap(jnp.einsum("bqhd,bkhd->bhqk", qf, pk))
        pmask = (jnp.arange(T)[None, :] < prefix_lens[:, None])  # [B, T]
        pmask = pmask[:, None, :]                                # [B, 1, T]
        if window > 0:
            # Query row r (abs pos prefix_lens + r) sees prefix key c
            # (abs pos c) iff prefix_lens + r - c < window.
            dist = (prefix_lens[:, None, None] + rows
                    - jnp.arange(T)[None, None, :])   # [B, S, T]
            pmask = pmask & (dist < window)
        else:
            pmask = jnp.broadcast_to(pmask, (B, S, T))
        ps_scores = jnp.where(pmask[:, None, :, :], ps_scores, _NEG_INF)
        scores = jnp.concatenate([ps_scores, ss], axis=-1)
        values = jnp.concatenate([pv, vf], axis=1)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, values)

    def _with_prefix(_):
        # Span-bucketed prefix gather (same ladder as paged_attention_xla):
        # the prefix term only needs pages covering positions < prefix_len,
        # so a chunked long prefill stops re-gathering its table's FULL
        # span on every chunk. Accelerator-gated like the decode ladder —
        # each span is a compiled variant, noise the CPU suite can't pay.
        page_size = pool.shape[4]
        max_pages = page_table.shape[1]
        spans = []
        if _span_buckets_on():
            s_ = max_pages
            while s_ > 1 and len(spans) < 3:
                spans.append(s_)
                s_ = -(-s_ // 2)
        spans = sorted(set(spans + [max_pages]))
        if len(spans) == 1:
            return _attend_prefix(page_table)
        need = jnp.max(-(-prefix_lens // page_size))
        idx = sum((need > sp).astype(jnp.int32) for sp in spans[:-1])
        branches = [lambda _, sp=sp: _attend_prefix(page_table[:, :sp])
                    for sp in spans]
        return jax.lax.switch(idx, branches, operand=None)

    # The prefix term gathers the row's whole page span and scores
    # against it — real bandwidth and FLOPs that a no-cache-hit prefill
    # (prefix 0, the common serving admission) would spend entirely on
    # fully-masked keys. Runtime-branch it: XLA compiles both sides, the
    # device executes only the live one.
    out = jax.lax.cond(jnp.any(prefix_lens > 0), _with_prefix,
                       _suffix_only, operand=None)
    return out.astype(q.dtype)


def _pallas_interpret() -> bool:
    """XLLM_PALLAS_INTERPRET=1 runs the Pallas kernels in interpret mode
    and lets the dispatch gates treat the CPU backend as kernel-capable —
    so tests exercise the REAL kernel routing hermetically (slow; tiny
    shapes only)."""
    import os

    return os.environ.get("XLLM_PALLAS_INTERPRET", "") == "1"


def attention_path(backend: str, interpret: bool, head_dim: int,
                   n_heads: int, n_kv: int, dtype, tp: int = 1,
                   context_parallel: bool = False) -> str:
    """The path `paged_attention` takes, from what the code can observe
    while it traces; the string is what `/stats`.attention_paths records
    (chipbench's `decode_paths` check and chip_smoke.py read it).

    One eligibility rule for the hand-written kernels: Mosaic tiling
    needs the head dim to be a lane-width multiple and GQA an integer
    group size, in bf16 or f32; the CPU backend runs them in interpret
    mode only. A pool sharded over the seq axis goes through the
    context-parallel op (kernel or dense body by the same rule); head
    counts that do not divide over `tp` leave the kernel, which runs per
    head-shard."""
    dtype = jnp.dtype(dtype)
    kernel_ok = (head_dim % 128 == 0 and n_heads % n_kv == 0
                 and dtype in (jnp.bfloat16, jnp.float32)
                 and (backend != "cpu" or interpret))
    if context_parallel:
        return (f"cp-pallas ({AXIS_SEQ})" if kernel_ok
                else f"cp-xla-dense ({AXIS_SEQ})")
    if not kernel_ok:
        why = ("cpu backend" if backend == "cpu"
               else f"shape outside the kernel's tiling: hd={head_dim} "
                    f"heads={n_heads}/{n_kv} dtype={dtype}")
        return f"xla ({why})"
    if n_kv % tp or n_heads % tp:
        return f"xla (kv heads {n_kv} do not divide over tp={tp})"
    return "pallas" if tp == 1 else f"pallas (shard_map model={tp})"


def decode_attention_step(q: jax.Array, k: jax.Array, v: jax.Array,
                          pool: jax.Array, layer: int,
                          page_table: jax.Array, context_lens: jax.Array,
                          scale: float | None = None,
                          softcap: float = 0.0, window: int = 0,
                          ) -> tuple[jax.Array, jax.Array]:
    """Append one token's K/V to layer `layer` of the pool and attend, as
    one step: the one decode append+attend path of every family.

    q: [B, n_heads, hd]; k/v: [B, n_kv, hd] — the new token, written at
    position ``context_lens[b] - 1`` (context_lens INCLUDE it, matching
    the engine decode path's ``positions = clens - 1``); attention covers
    positions < ``context_lens[b]``. Returns (attn [B, n_heads, hd],
    pool).
    """
    pool = write_kv(pool, layer, k[:, None], v[:, None], page_table,
                    context_lens - 1, jnp.ones_like(context_lens))
    attn = paged_attention(q, pool, layer, page_table, context_lens,
                           scale=scale, softcap=softcap, window=window)
    return attn, pool


# ------------------------------------------------------------ decode attn
def _span_buckets_on() -> bool:
    """Span-bucketed gathers compile up to 4 variants of the attention
    subgraph per program — worth it on accelerators (bandwidth saved
    every step), pure compile-time cost on the CPU test backend (the
    suite pays minutes)."""
    return _backend() != "cpu"


def paged_attention_xla(q: jax.Array, pool: jax.Array, layer: int,
                        page_table: jax.Array,
                        context_lens: jax.Array,
                        scale: float | None = None,
                        softcap: float = 0.0, window: int = 0) -> jax.Array:
    """One-token-per-sequence paged attention (XLA path).

    q: [B, n_heads, hd]; pool: [L, 2, num_pages, n_kv, ps, hd], read at
    `layer`; returns [B, n_heads, hd]. Assumes the new token's
    K/V are already written (attends to positions < context_lens[b] + 1 ...
    callers pass context_lens *including* the new token). softcap/window:
    gemma-2 score capping and sliding-window (the query sits at position
    context_lens[b]-1, so the window keeps keys >= context_lens[b]-window).

    The gather is span-bucketed: a static pow2 ladder of page-table
    prefixes compiles once each and `lax.switch` picks the shortest one
    covering the longest live context — families on this path (the MLA
    latent cache, whose fused head dim doesn't fit the Pallas kernel's
    tiling) no longer pay a FULL-table gather per layer per step when
    the table is sized for contexts far beyond current occupancy.
    """
    B, n_heads, hd = q.shape
    n_kv = pool.shape[3]
    n_rep = n_heads // n_kv
    page_size = pool.shape[4]
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    qf = q.astype(jnp.float32) * scale

    def attend(pt_prefix):
        k, v = (_repeat_kv(g, n_rep)
                for g in gather_pages(pool, layer, pt_prefix))
        T = k.shape[1]
        scores = jnp.einsum("bhd,bkhd->bhk", qf, k.astype(jnp.float32))
        if softcap > 0:
            scores = softcap * jnp.tanh(scores / softcap)
        mask = jnp.arange(T)[None, :] < context_lens[:, None]
        if window > 0:
            mask = mask & (jnp.arange(T)[None, :]
                           >= context_lens[:, None] - window)
        scores = jnp.where(mask[:, None, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhk,bkhd->bhd", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)

    max_pages = page_table.shape[1]
    # Pow2 span ladder, smallest-first (at most 4 variants; tiny tables
    # — and the CPU test backend — keep the single full-span branch).
    spans = []
    if _span_buckets_on():
        s = max_pages
        while s > 1 and len(spans) < 3:
            spans.append(s)
            s = -(-s // 2)
    spans = sorted(set(spans + [max_pages]))
    if len(spans) == 1:
        return attend(page_table)

    need = jnp.max(-(-context_lens // page_size))    # pages to cover
    idx = sum((need > s).astype(jnp.int32) for s in spans[:-1])
    branches = [lambda _, s_=s_: attend(page_table[:, :s_])
                for s_ in spans]
    return jax.lax.switch(idx, branches, operand=None)


def paged_attention(q: jax.Array, pool: jax.Array, layer: int,
                    page_table: jax.Array,
                    context_lens: jax.Array,
                    scale: float | None = None,
                    softcap: float = 0.0, window: int = 0) -> jax.Array:
    """Dispatcher over layer `layer` of the pool
    [L, 2, num_pages, n_kv, ps, hd], on `attention_path`'s word:
    context-parallel op when the program's mesh shards the pool over the
    seq axis, hand-written Pallas kernel on TPU, XLA gather elsewhere
    (CPU test meshes) and for shapes outside the kernel's tiling
    constraints. Selection happens at trace time — all paths are
    numerically equivalent (tested). The kernel and the gather both read
    the pool where it lies; only the CP op still takes one layer's views.
    softcap/window (gemma-2) ride the Pallas kernel as static params
    when the shape qualifies, falling back to XLA otherwise; CP meshes
    refuse such models (the partial-stats merge has no softcap/window
    support)."""
    mesh, tp = _axis_mesh(AXIS_MODEL)
    seq_mesh, _ = _axis_mesh(AXIS_SEQ)
    path = attention_path(_backend(), _pallas_interpret(), q.shape[-1],
                          q.shape[-2], pool.shape[3], q.dtype, tp,
                          context_parallel=seq_mesh is not None)
    note_path("paged_attention", path)
    if seq_mesh is not None:
        if softcap != 0.0 or window != 0:
            raise NotImplementedError(
                "context-parallel decode does not support attn "
                "softcap/sliding window")
        from .cp_paged_attention import cp_paged_attention

        return cp_paged_attention(q, pool[layer, 0], pool[layer, 1],
                                  page_table, context_lens, seq_mesh,
                                  seq_axis=AXIS_SEQ, scale=scale)
    if path.startswith("xla"):
        return paged_attention_xla(q, pool, layer, page_table, context_lens,
                                   scale=scale, softcap=softcap,
                                   window=window)

    from .pallas_paged_attention import paged_attention_pallas

    # softcap/window/scale are static kernel params (gemma-2 decodes
    # through the kernel too — the XLA fallback gathers every row's FULL
    # page span dense per layer per step).
    kernel = functools.partial(paged_attention_pallas,
                               interpret=_pallas_interpret(),
                               scale=scale, softcap=softcap, window=window)
    return _on_head_shards(kernel, mesh, P(None, AXIS_MODEL, None), 3)(
        q, pool, jnp.full((1,), layer, jnp.int32), page_table,
        context_lens)
