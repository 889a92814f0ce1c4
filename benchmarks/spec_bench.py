"""Speculative-decoding benchmark: tok/s with prompt-lookup speculation
on vs off, greedy, repetitive workload (where lookahead drafts accept).
Run on TPU for real numbers; CPU runs validate the mechanism only.

Prints one JSON line per mode plus the speedup.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    import jax

    from xllm_service_tpu.common.request import SamplingParams
    from xllm_service_tpu.engine.config import EngineConfig
    from xllm_service_tpu.engine.engine import EngineRequest, InferenceEngine
    from xllm_service_tpu.models.base import bench_1b_config

    from _chip import require_tpu

    device = require_tpu()
    mcfg = bench_1b_config()
    B = 8
    # Budgets ample enough that the timed window is pure steady state (no
    # budget-bounded horizon shrink -> no tail compiles in the window).
    ctx, new = 256, 640
    max_seq = 1024

    # Repetitive prompts (the prompt-lookup draft's home turf — code/JSON
    # style repetition).
    base_unit = list(range(11, 11 + 8))
    prompt = (base_unit * (ctx // len(base_unit)))[:ctx]

    results = {}
    for spec_k in (0, 4):
        cfg = EngineConfig(
            model_id="spec-bench", model=mcfg,
            num_pages=(B * max_seq) // 16 + 64, page_size=16,
            max_batch_size=B, max_seq_len=max_seq,
            prefill_buckets=(64, 256, max_seq),
            hash_block_size=128,
            decode_horizon=8 if spec_k == 0 else 1,
            speculate_k=spec_k)
        engine = InferenceEngine(cfg)
        counts = {"tokens": 0}

        def on_output(out):
            counts["tokens"] += sum(len(s.token_ids) for s in out.outputs)

        for i in range(B):
            engine.submit(EngineRequest(
                f"s{i}", token_ids=list(prompt) + [i],
                sampling=SamplingParams(max_tokens=new, temperature=0.0,
                                        ignore_eos=True),
                on_output=on_output))
        # Warm up admission + compile the decode/verify programs (a few
        # steps) so XLA compiles stay out of the timed window.
        while engine._waiting:
            engine.step()
        for _ in range(3):
            engine.step()
        # Steady-state window: fixed step count at full batch.
        n_steps = 10
        t0 = time.perf_counter()
        start_toks = counts["tokens"]
        for _ in range(n_steps):
            engine.step()
        dt = time.perf_counter() - t0
        toks = counts["tokens"] - start_toks
        results[spec_k] = toks / dt
        print(json.dumps({"mode": f"speculate_k={spec_k}",
                          "tok_per_s": round(toks / dt, 2),
                          "tokens": toks}))
        engine.stop()

    print(json.dumps({"metric": "speculative_speedup",
                      "value": round(results[4] / results[0], 3),
                      "unit": "x",
                      "device": device}))


if __name__ == "__main__":
    main()
