"""Context-parallel paged-decode kernel benchmark.

On the one real chip this Mosaic-validates the CP partial-stats Pallas
kernel (ops/cp_paged_attention.py) and A/Bs three bodies at bench-1b
attention shapes:

  1. single-device decode kernel (ops/pallas_paged_attention) — the
     non-CP reference number,
  2. cp_paged_attention with the Pallas partial kernel (1-device mesh:
     same math, full shard_map + psum-merge machinery),
  3. cp_paged_attention with the dense-gather XLA fallback body.

Prints one JSON line with per-body step times. A Mosaic compile failure
in (2) surfaces as an "error" field — exactly what the sweep exists to
catch (the kernel has only ever compiled under interpret=True on CPU).
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def _time(fn, *args, iters=50):
    out = fn(*args)
    jax_block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax_block(out)
    return (time.perf_counter() - t0) / iters * 1e3   # ms/step


def jax_block(x):
    import jax
    jax.block_until_ready(x)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from xllm_service_tpu.ops.cp_paged_attention import cp_paged_attention
    from xllm_service_tpu.ops.pallas_paged_attention import (
        paged_attention_pallas,
    )

    from _chip import require_tpu

    device = require_tpu()

    # bench-1b attention shapes (models/base.py bench_1b_config).
    B, n_q, n_kv, hd, ps = 16, 16, 8, 128, 16
    ctx = int(os.environ.get("XLLM_CP_CTX", "0")) or 2048
    if ctx > 8192:
        B = max(2, B // 4)   # keep the pool inside one chip's HBM
    pages_per_seq = ctx // ps
    num_pages = B * pages_per_seq + 64
    dtype = jnp.bfloat16

    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, n_q, hd), dtype)
    k_pages = jax.random.normal(key, (num_pages, n_kv, ps, hd), dtype)
    v_pages = jax.random.normal(key, (num_pages, n_kv, ps, hd), dtype)
    pt = np.zeros((B, pages_per_seq + 4), np.int32)
    for b in range(B):
        pt[b, :pages_per_seq] = rng.permutation(
            np.arange(num_pages - 64))[:pages_per_seq]
    page_table = jnp.asarray(pt)
    clens = jnp.full((B,), ctx, jnp.int32)

    mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))

    result = {"device": device, "B": B, "ctx": ctx,
              "metric": "cp_decode_attention_ms_per_step", "unit": "ms"}

    # 1. single-device decode kernel (reference point).
    single = jax.jit(paged_attention_pallas)
    pool = jnp.stack([k_pages, v_pages])[None]     # one layer's pool
    result["single_device_kernel_ms"] = round(
        _time(single, q, pool, jnp.zeros((1,), jnp.int32), page_table,
              clens), 4)

    # 2. CP Pallas partial kernel (Mosaic on accel; the validation target).
    def cp(qq, kk, vv, tt, cc):
        return cp_paged_attention(qq, kk, vv, tt, cc, mesh=mesh)

    os.environ.pop("XLLM_DISABLE_PALLAS_ATTENTION", None)
    try:
        cp_pallas = jax.jit(cp)
        result["cp_pallas_ms"] = round(
            _time(cp_pallas, q, k_pages, v_pages, page_table, clens), 4)
    except Exception as e:  # noqa: BLE001 — Mosaic failure is the finding
        result["error"] = f"cp pallas kernel: {type(e).__name__}: {e}"[:400]

    # 3. dense XLA fallback body.
    os.environ["XLLM_DISABLE_PALLAS_ATTENTION"] = "1"
    try:
        cp_xla = jax.jit(lambda *a: cp(*a))
        result["cp_xla_fallback_ms"] = round(
            _time(cp_xla, q, k_pages, v_pages, page_table, clens), 4)
    finally:
        os.environ.pop("XLLM_DISABLE_PALLAS_ATTENTION", None)

    if "cp_pallas_ms" in result and "cp_xla_fallback_ms" in result:
        result["pallas_vs_xla"] = round(
            result["cp_xla_fallback_ms"] / result["cp_pallas_ms"], 3)
        result["value"] = result["cp_pallas_ms"]

    # Parity check between the two CP bodies (and vs single-device).
    try:
        a = np.asarray(jax.jit(cp)(q, k_pages, v_pages, page_table, clens),
                       np.float32)
        os.environ["XLLM_DISABLE_PALLAS_ATTENTION"] = "1"
        b = np.asarray(
            jax.jit(lambda *x: cp(*x))(q, k_pages, v_pages, page_table,
                                       clens), np.float32)
        os.environ.pop("XLLM_DISABLE_PALLAS_ATTENTION", None)
        result["parity_max_abs_diff"] = float(np.max(np.abs(a - b)))
    except Exception as e:  # noqa: BLE001
        result.setdefault("error", f"parity: {type(e).__name__}: {e}"[:300])

    print(json.dumps(result))


if __name__ == "__main__":
    main()
