#!/usr/bin/env python3
"""Described-chip sizing of a configuration: compile the engine's decode
and prefill programs for a v5e chip that is not attached and print what
`memory_analysis()` says. Memory and HLO facts only, never a time.

    JAX_PLATFORMS=cpu python3 chipbench/sizing.py qwen25-7b-int8 [bucket ...]

Uses the program's own `benchmarks/compile_gate.py` (a builder's tool: the
benchmark's runs never import it). One JSON line per program.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    from benchmarks import compile_gate as gate
    from chipbench.engine_setup import build_engine_config

    name = sys.argv[1]
    ecfg, eng = build_engine_config(HERE / "configs" / name, 0, name)
    buckets = [int(b) for b in sys.argv[2:]] or list(ecfg.prefill_buckets)
    dev = gate.describe_devices()[0]
    out = gate.compile_engine_programs(
        ecfg, device=dev, horizons=(ecfg.decode_horizon,), buckets=buckets)
    for prog, facts in out.items():
        print(json.dumps({"config": name, "program": prog, "facts": facts}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
