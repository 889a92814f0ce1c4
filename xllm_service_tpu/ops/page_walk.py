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
