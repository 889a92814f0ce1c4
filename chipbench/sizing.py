#!/usr/bin/env python3
"""Described-chip sizing of a configuration: compile the engine's decode
and prefill programs for a v5e chip that is not attached and print what
`memory_analysis()` says. Memory and HLO facts only, never a time.

    JAX_PLATFORMS=cpu python3 chipbench/sizing.py qwen25-7b-int8 [bucket ...]

Uses the program's own `benchmarks/compile_gate.py` (a builder's tool: the
benchmark's runs never import it). One JSON line per program, then one that
holds the decode program's arguments (weights, pool and state: what stays on
the chip while it serves) against the chip's memory and the driver's floors
for a cell's size, beside the bytes of the tree the configuration's family
makes: the numbers to know before asking for chip time.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


# Of one chip's memory by `memory_peak_bytes`: what the driver asks of a new
# cell, and what it asks where the chip is busy 75% of the traced window.
FLOOR_PCT, FLOOR_BUSY_PCT = 25.0, 12.5


def size_line(name: str, family, hf: dict, eng: dict, facts: dict,
              hbm_bytes: float) -> dict:
    """The last line: `facts` are the decode program's."""
    import jax

    tree = family.weights.param_shapes(hf, eng["weights"])
    args = facts["argument_gib"] * 2 ** 30
    return {"config": name, "family": family.name or "default",
            "family_weights_bytes": sum(
                x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)),
            "decode_arguments_bytes": args, "hbm_bytes": hbm_bytes,
            "arguments_pct_of_hbm": 100.0 * args / hbm_bytes,
            "floor_pct": FLOOR_PCT,
            "floor_pct_where_busy_75": FLOOR_BUSY_PCT}


def main() -> int:
    from benchmarks import compile_gate as gate
    from chipbench import harness, peaks
    from chipbench.engine_setup import build_engine_config

    name = sys.argv[1]
    _, search = harness.load_bench(harness.ROOT / "BENCHMARK.json")
    config_dir = harness._find(search, "configs", name)
    ecfg, eng = build_engine_config(config_dir, 0, name)
    buckets = [int(b) for b in sys.argv[2:]] or list(ecfg.prefill_buckets)
    dev = gate.describe_devices()[0]
    out = gate.compile_engine_programs(
        ecfg, device=dev, horizons=(ecfg.decode_horizon,), buckets=buckets)
    for prog, facts in out.items():
        print(json.dumps({"config": name, "program": prog, "facts": facts}),
              flush=True)
    hf = json.loads((config_dir / "config.json").read_text())
    print(json.dumps(size_line(
        name, harness.family_of(search, hf), hf, eng,
        out[f"decode_multi_h{ecfg.decode_horizon}"],
        peaks.lookup("TPU v5 lite")["hbm_bytes"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
