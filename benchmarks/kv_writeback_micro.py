"""Micro-bench: decode-step KV writeback strategies.

Compares the current per-layer `jnp.stack + dynamic_update_index_in_dim`
pool writeback against a direct full-pool scatter
(`kv.at[l, :, page_idx, :, slot, :]`). Needs the TPU: a CPU timing of a
device writeback says nothing.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import time

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial


def run(L=4, pages=1024, n_kv=4, ps=16, hd=64, B=8, steps=30):
    from _chip import require_tpu

    device = require_tpu()
    rng = np.random.default_rng(0)
    kv = jnp.zeros((L, 2, pages, n_kv, ps, hd), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, n_kv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, n_kv, hd)), jnp.float32)
    page_idx = jnp.asarray(rng.integers(0, pages, B), jnp.int32)
    slot = jnp.asarray(rng.integers(0, ps, B), jnp.int32)

    @partial(jax.jit, donate_argnums=(0,))
    def step_stack(kv, k, v):
        for l in range(L):
            k_pages, v_pages = kv[l, 0], kv[l, 1]
            k_pages = k_pages.at[page_idx, :, slot, :].set(k, mode="drop")
            v_pages = v_pages.at[page_idx, :, slot, :].set(v, mode="drop")
            s = jnp.sum(k_pages[page_idx, :, slot, :] * v_pages[page_idx, :, slot, :])
            k = k + s * 1e-9   # data dependence so layers serialize
            kv = jax.lax.dynamic_update_index_in_dim(
                kv, jnp.stack([k_pages, v_pages]), l, 0)
        return kv, k

    @partial(jax.jit, donate_argnums=(0,))
    def step_scatter(kv, k, v):
        for l in range(L):
            kv = kv.at[l, 0, page_idx, :, slot, :].set(k, mode="drop")
            kv = kv.at[l, 1, page_idx, :, slot, :].set(v, mode="drop")
            s = jnp.sum(kv[l, 0, page_idx, :, slot, :] * kv[l, 1, page_idx, :, slot, :])
            k = k + s * 1e-9
        return kv, k

    for name, fn in [("stack+dynupd", step_stack), ("direct-scatter", step_scatter)]:
        pool = jnp.zeros((L, 2, pages, n_kv, ps, hd), jnp.float32)
        pool, kk = fn(pool, k, v)   # compile
        jax.block_until_ready(pool)
        t0 = time.perf_counter()
        for _ in range(steps):
            pool, kk = fn(pool, k, kk)
        jax.block_until_ready(pool)
        dt = (time.perf_counter() - t0) / steps
        import json
        print(json.dumps({"variant": name, "ms_per_step": round(dt * 1e3, 3),
                          "pool_mb": round(pool.nbytes / 1e6),
                          "device": device}))


if __name__ == "__main__":
    run()
