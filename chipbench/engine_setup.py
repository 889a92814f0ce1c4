"""A configuration directory -> the program's own ModelConfig and
EngineConfig. The only place where the benchmark's data files meet the
program's constructors; used by the process that holds the chip
(agent_main.py) and by the described-chip sizing (sizing.py)."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

ENGINE_KEYS = {"weights", "num_pages", "page_size", "hash_block_size",
               "max_batch_size", "max_seq_len", "prefill_buckets",
               "decode_horizon", "warmup_programs", "tp", "replicas"}


def read_engine_json(config_dir: Path) -> dict:
    eng = json.loads((Path(config_dir) / "engine.json").read_text())
    unknown = set(eng) - ENGINE_KEYS
    if unknown or ENGINE_KEYS - set(eng):
        raise ValueError(f"{config_dir}/engine.json: unknown keys "
                         f"{sorted(unknown)}, missing "
                         f"{sorted(ENGINE_KEYS - set(eng))}")
    if eng["weights"] not in ("int8", "bfloat16"):
        raise ValueError(f"engine.json weights {eng['weights']!r}: the "
                         "served types are int8 and bfloat16")
    return eng


def build_engine_config(config_dir: Path, seed: int, model_id: str):
    """(EngineConfig, engine.json dict) for the configuration as it is run:
    every shape from config.json through the program's own
    model_config_from_hf, nothing overridden but the served weight type."""
    from xllm_service_tpu.common.types import InstanceType
    from xllm_service_tpu.engine.config import EngineConfig
    from xllm_service_tpu.models.hf_config import model_config_from_hf
    from xllm_service_tpu.parallel.mesh import MeshConfig

    eng = read_engine_json(config_dir)
    mcfg = model_config_from_hf(config_dir)
    if eng["weights"] == "int8":
        mcfg = dataclasses.replace(mcfg, quant="int8")
    ecfg = EngineConfig(
        model_id=model_id, model=mcfg, model_family=mcfg.name,
        num_pages=eng["num_pages"], page_size=eng["page_size"],
        hash_block_size=eng["hash_block_size"],
        max_batch_size=eng["max_batch_size"],
        max_seq_len=eng["max_seq_len"],
        prefill_buckets=tuple(eng["prefill_buckets"]),
        decode_horizon=eng["decode_horizon"],
        warmup_programs=bool(eng["warmup_programs"]),
        role=InstanceType.MIX,
        # int32 on the program's side; weights take the whole seed.
        seed=seed % (2 ** 31 - 1))
    if eng["tp"] > 1:
        ecfg.mesh = MeshConfig(model=eng["tp"])
    return ecfg, eng
