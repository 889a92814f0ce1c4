"""Steady-state decode throughput of the engine on the attached TPU.

One measurement: the engine's jit decode step (paged attention + sampling)
at full batch, closed loop, after admission — and ONE JSON line:

    {"metric": "decode_tokens_per_sec_per_chip", "value": N, "unit": "tok/s",
     "device": {"platform": "tpu", "kind": "...", "count": 1},
     "pct_roofline": P, "effective_gbps": G, "compilations_in_window": 0, ...}

It needs the chip. Under `JAX_PLATFORMS=cpu`, or where JAX finds no
accelerator, it exits non-zero and prints no metric: a CPU number is never
written under a device metric's name. Any failure is an exception and a
non-zero exit, not a JSON line with rc 0.

``pct_roofline``: decode at serving batch is HBM-bandwidth-bound, so the
ceiling is the weight+KV stream per token-step against the chip's HBM
bandwidth, from a table keyed by ``device_kind``; a kind that is not in the
table is an error, not a default.

This is not a benchmark cell (ROADMAP S2 defines those): no arrivals, no
prefill in the window, one llama shape. The driver's ledger keeps history.

Model selection: XLLM_BENCH_MODEL=1b (default) | 8b | moe — 8b is
Llama-3-8B shapes, moe is the MLA+MoE bench shape; both force weight-only
int8 unless XLLM_QUANT is set explicitly (bf16 doesn't fit / leaves no KV
headroom on the 16 GB v5e). XLLM_BENCH_CTX sets a long-context variant.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

METRIC = "decode_tokens_per_sec_per_chip"

# Published per-chip peaks, keyed by jax's device_kind (Google Cloud
# documentation, "TPU v5e": 16 GB HBM at 819 GB/s, 197 TFLOP/s bf16).
PEAKS = {
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
    "TPU v5e": {"hbm_gbps": 819.0, "bf16_tflops": 197.0},
}


def _bench_variant() -> str:
    """Non-default kernel/route knobs that change what is measured, kept
    in the record so an A/B arm is never read as the default config."""
    parts = []
    if os.environ.get("XLLM_PREFILL_PALLAS", ""):
        parts.append("prefill_pallas")
    if os.environ.get("XLLM_MQ_PALLAS", ""):
        parts.append("mq_pallas")
    pc = os.environ.get("XLLM_PAGE_CHUNK", "")
    if pc:
        parts.append(f"chunk={pc}")
    if os.environ.get("XLLM_PAGE_PIPELINE", "") == "row":
        parts.append("rowpipe")
    return ",".join(parts)


def main() -> int:
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from _chip import require_tpu

    device = require_tpu()      # exits non-zero without the chip
    if device["kind"] not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device['kind']!r}; add it to PEAKS with its "
                       "source")
    peaks = PEAKS[device["kind"]]
    import jax

    from xllm_service_tpu.common.request import SamplingParams
    from xllm_service_tpu.engine.config import EngineConfig
    from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
    from xllm_service_tpu.models.base import bench_1b_config, llama3_8b_config

    model_key = os.environ.get("XLLM_BENCH_MODEL", "1b")
    # 8b and moe default to weight-only int8 (bf16 doesn't fit/leaves no
    # KV headroom on a 16 GB chip).
    quant = os.environ.get("XLLM_QUANT",
                           "int8" if model_key in ("8b", "moe") else "")
    if model_key == "8b":
        mcfg = llama3_8b_config()
    elif model_key == "moe":
        from xllm_service_tpu.models.deepseek_moe import bench_moe_config
        mcfg = bench_moe_config()
    elif model_key == "1b":
        mcfg = bench_1b_config()
    else:
        raise ValueError(f"XLLM_BENCH_MODEL={model_key!r}: 1b, 8b or moe")
    if quant:
        import dataclasses

        mcfg = dataclasses.replace(mcfg, quant=quant)

    B, ctx, max_seq = 16, 512, 1024
    ctx_variant = ""
    if os.environ.get("XLLM_BENCH_CTX", ""):
        # Long-context decode variant: the page walk dominates here, so
        # this is where the paged-kernel/DMA knobs actually show.
        # Batch shrinks to keep the KV pool inside one chip's HBM.
        ctx = int(os.environ["XLLM_BENCH_CTX"])
        if ctx + 512 > mcfg.max_context_len:
            # Widen the model's rope window to fit the requested context —
            # same weights/shapes otherwise, so the paged-walk depth is the
            # only variable.
            import dataclasses as _dc
            mcfg = _dc.replace(mcfg, max_context_len=ctx + 512)
        B = (16 if ctx <= 512 else 8 if ctx <= 1024 else
             4 if ctx <= 4096 else 2 if ctx <= 16384 else 1)
        max_seq = ctx + 512
        ctx_variant = f"ctx={ctx}"
    cfg = EngineConfig(
        model_id=f"bench-{model_key}", model=mcfg,
        model_family=mcfg.name,
        num_pages=(B * max_seq) // 16 + 64, page_size=16,
        max_batch_size=B, max_seq_len=max_seq,
        prefill_buckets=(128, 512, max_seq), hash_block_size=128,
        decode_horizon=32)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(10, mcfg.vocab_size - 10, ctx).tolist()
               for _ in range(B)]
    counts = {"tokens": 0}

    def on_output(out):
        counts["tokens"] += sum(len(s.token_ids) for s in out.outputs)

    engine = InferenceEngine(cfg)
    # Admit all B sequences (prefill) — not timed; we measure decode.
    for i, p in enumerate(prompts):
        engine.submit(EngineRequest(
            f"bench-{i}", token_ids=p,
            sampling=SamplingParams(max_tokens=max_seq - ctx - 8,
                                    temperature=0.0, ignore_eos=True),
            on_output=on_output))
    admit_deadline = time.perf_counter() + 600
    while engine._waiting or len(engine._running) < B:
        engine.step()
        if not engine._waiting and engine._running:
            break
        if time.perf_counter() > admit_deadline:
            raise TimeoutError("admission stalled")

    # Warmup decode steps (compile + cache).
    for _ in range(2):
        engine.step()

    # The window: each engine.step() fetches its packed output to the
    # host, so the clock stops on finished device work, not on enqueue.
    compiles = {"n": 0}
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.__setitem__(
            "n", compiles["n"] + ("backend_compile" in name)))
    n_steps = 10   # horizons (tokens/step = horizon)
    start = counts["tokens"]
    t0 = time.perf_counter()
    for _ in range(n_steps):
        engine.step()
    dt = time.perf_counter() - t0
    generated = counts["tokens"] - start
    toks_per_s = generated / dt

    # Roofline: HBM bytes per decode token-step = one full weight stream
    # + per-sequence KV read at the mid-run context length.
    mid_ctx = ctx + generated // (2 * B)
    bytes_per_tok_step = (mcfg.decode_weight_stream_bytes()
                          + B * mcfg.kv_bytes_per_token(mid_ctx))
    eff_gbps = bytes_per_tok_step * (toks_per_s / B) / 1e9
    variant = ",".join(p for p in (_bench_variant(), ctx_variant) if p)
    result = {
        "metric": METRIC,
        "value": round(toks_per_s, 2),
        "unit": "tok/s",
        "device": device,
        "model": model_key,
        "batch": B,
        "context": ctx,
        "window_s": round(dt, 3),
        "compilations_in_window": compiles["n"],
        "bytes_per_step_mb": round(bytes_per_tok_step / 1e6, 1),
        "effective_gbps": round(eff_gbps, 1),
        "pct_roofline": round(100.0 * eff_gbps / peaks["hbm_gbps"], 1),
        "attention_paths": engine.stats()["attention_paths"],
    }
    if mcfg.quant:
        result["quant"] = mcfg.quant
    if variant:
        result["variant"] = variant
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
