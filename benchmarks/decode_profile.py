"""Decode-step component profile: names where the decode token-step time
goes on the attached accelerator.

This bench times the decode step's components in isolation at bench.py's
shapes (bench-1b, B=16), to attribute what the whole step's time is not
explained by the weight stream (ROADMAP S4):

  - full_step: fam.decode_forward + sample (what bench.py times)
  - forward_only: fam.decode_forward alone
  - attention_only: the paged-attention op over the same pool (isolated,
    scaled by n_layers)
  - sampling_only: sample_tokens on random logits
  - matmul_and_rest_ms (derived): forward_only - attention_only — the
    layer matmuls PLUS norms/rope/KV-writeback/dispatch gaps
  - sample_overhead_ms (derived): full_step - forward_only
  - dispatch_fetch_rtt_ms / upload_32kb_ms: the per-program-call floor
    between this host and its chip

Prints ONE JSON line. Needs the TPU (host-clock timings of components in
isolation; the profiler trace of ROADMAP S3 supersedes it).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def bench_fn(fn, *args, iters=30):
    out = fn(*args)
    jax_block(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax_block(out)
    return (time.perf_counter() - t0) / iters * 1e3


def jax_block(x):
    import jax
    jax.block_until_ready(x)


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from xllm_service_tpu.engine.sampling import SamplingState, sample_tokens
    from xllm_service_tpu.models import get_model_family
    from xllm_service_tpu.models.base import bench_1b_config
    from xllm_service_tpu.ops.attention import paged_attention

    from _chip import require_tpu

    device = require_tpu()
    mcfg = bench_1b_config()
    fam = get_model_family(mcfg.name)

    B, ctx, ps = 16, 512, 16
    pages_per_seq = -(-1024 // ps)
    num_pages = B * pages_per_seq + 64

    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    params = fam.init_params(mcfg, key)

    kv = jnp.zeros((mcfg.num_layers, 2, num_pages, mcfg.num_kv_heads, ps,
                    mcfg.head_dim), mcfg.dtype)
    pt = np.full((B, pages_per_seq), num_pages - 1, np.int32)
    for b in range(B):
        pt[b] = rng.permutation(np.arange(num_pages - 64))[:pages_per_seq]
    page_table = jnp.asarray(pt)
    clens = jnp.full((B,), ctx, jnp.int32)
    tokens = jnp.asarray(rng.integers(10, mcfg.vocab_size - 10, B),
                         jnp.int32)
    positions = clens - 1

    result = {"device": device, "B": B, "ctx": ctx,
              "model": "1b",
              "metric": "decode_step_component_ms", "unit": "ms"}

    # 1. forward_only (returns logits + new kv; donation off for timing).
    fwd = jax.jit(lambda p, t, pos, k, tab, cl: fam.decode_forward(
        p, mcfg, t, pos, k, tab, cl)[0])
    result["forward_only_ms"] = round(bench_fn(
        fwd, params, tokens, positions, kv, page_table, clens), 3)

    def greedy_state():
        import dataclasses

        # Greedy = temperature 0 (the common serving case bench.py runs).
        return dataclasses.replace(
            SamplingState.init(B, mcfg.vocab_size),
            temperature=jnp.zeros((B,), jnp.float32))

    # 2. full step: forward + greedy sample.
    def full(p, t, pos, k, tab, cl, keys):
        logits, _ = fam.decode_forward(p, mcfg, t, pos, k, tab, cl)
        toks, _ = sample_tokens(logits.astype(jnp.float32),
                                greedy_state(), keys, cl)
        return toks

    keys = jax.random.split(key, B)
    result["full_step_ms"] = round(bench_fn(
        jax.jit(full), params, tokens, positions, kv, page_table, clens,
        keys), 3)

    # 3. attention_only over one layer's pool, scaled by n_layers.
    q = jax.random.normal(key, (B, mcfg.num_heads, mcfg.head_dim),
                          mcfg.dtype)
    attn = jax.jit(lambda qq, kk, vv, tab, cl: paged_attention(
        qq, kk, vv, tab, cl))
    per_layer = bench_fn(attn, q, kv[0, 0], kv[0, 1], page_table, clens)
    result["attention_only_ms"] = round(per_layer * mcfg.num_layers, 3)
    result["attention_per_layer_ms"] = round(per_layer, 4)

    # 4. sampling_only on random logits.
    logits = jax.random.normal(key, (B, mcfg.vocab_size), jnp.float32)

    def samp(lg, keys, cl):
        return sample_tokens(lg, greedy_state(), keys, cl)[0]

    result["sampling_only_ms"] = round(bench_fn(
        jax.jit(samp), logits, keys, clens), 3)

    # 5. Per-call overhead floor (serving pays a dispatch+fetch per
    # horizon call and ~3x per admission).
    tiny = jnp.zeros((8,), jnp.float32)
    bump = jax.jit(lambda x: x + 1)
    result["dispatch_fetch_rtt_ms"] = round(bench_fn(bump, tiny), 3)
    up = np.zeros((8192,), np.int32)   # ~an admission's packed upload

    def upload(_):
        return jax.device_put(up)

    result["upload_32kb_ms"] = round(bench_fn(upload, None), 3)

    # Derived attribution.
    result["matmul_and_rest_ms"] = round(
        result["forward_only_ms"] - result["attention_only_ms"], 3)
    result["sample_overhead_ms"] = round(
        result["full_step_ms"] - result["forward_only_ms"], 3)
    result["value"] = result["full_step_ms"]
    # Roofline context: ideal weight-stream time at this config.
    wbytes = mcfg.decode_weight_stream_bytes()
    result["weight_stream_mb"] = round(wbytes / 1e6, 1)
    result["ideal_weight_stream_ms"] = round(wbytes / 819e9 * 1e3, 3)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
