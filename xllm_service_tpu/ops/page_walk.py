"""The shape of the paged-attention kernels' page walk, free of Pallas so
that the engine's host loop can count with it: the chunk a ring slot holds
and the kernels' run rule over a host page-table row
(`ops/pallas_page_dma.make_chunk_dma` is the same rule on the device)."""

from __future__ import annotations

import numpy as np

PAGE_CHUNK = 16     # pages per ring slot, and per run copy


def page_chunk_size(max_pages: int) -> int:
    """Pages per double-buffered DMA chunk in the paged-attention
    kernels, clamped to the table. VMEM cost is
    4 * chunk * n_kv * ps * hd elements (two k/v double buffers)."""
    return max(1, min(PAGE_CHUNK, max_pages))


def walk_run_counts(row, n_pages: int, chunk: int) -> tuple[int, int]:
    """(chunks walked, chunks fetched as a run) for one page-table row of
    which the first `n_pages` entries are walked: `make_chunk_dma`'s rule
    in numpy. A run chunk is full and its pages step by +1, or by -1,
    from its first."""
    full = n_pages // chunk
    steps = np.diff(np.asarray(row[:full * chunk], np.int64)
                    .reshape(full, chunk), axis=1)
    runs = (steps == 1).all(axis=1) | (steps == -1).all(axis=1)
    return -(-n_pages // chunk), int(runs.sum())


PREFILL_TILE_STATE = 12 << 20   # bytes of VMEM a query tile's state may take


def prefill_query_tile(S: int, n_heads: int, head_dim: int,
                       itemsize: int) -> int:
    """Suffix rows in one query tile of the prefill kernel
    (`ops/pallas_prefill_attention.py`), or 0 where the shape has none and
    prefill keeps the XLA form. The largest of 128 ... 8 rows that divides
    the bucket, fills whole sublane tiles of the query's type (8 rows of
    float32, 16 of bfloat16) and keeps the tile's state under
    `PREFILL_TILE_STATE`: a row of every head holds its float32
    accumulator, the query three times (the pipeline's two blocks and the
    head-major copy), the output twice, and the lane-wide m and l. No more
    than 128 rows: a tile past `seq_len` is skipped whole, so a finer tile
    skips more of a bucket's padding, and a group of heads already stacks
    `group x` rows into each product."""
    row = n_heads * (head_dim * (4 + 5 * itemsize) + 2 * 128 * 4)
    for tq in (128, 64, 32, 16, 8):
        if (tq * itemsize >= 32 and S % tq == 0
                and tq * row <= PREFILL_TILE_STATE):
            return tq
    return 0
