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
    """engine.json: the eleven keys, and optionally `engine_config`, an
    object of further EngineConfig fields by name (`engine_config_kwargs`
    holds them to the program's own dataclass; this function stays off the
    program, and so off JAX, for the launcher's sake)."""
    eng = json.loads((Path(config_dir) / "engine.json").read_text())
    unknown = set(eng) - ENGINE_KEYS - {"engine_config"}
    if unknown or ENGINE_KEYS - set(eng):
        raise ValueError(f"{config_dir}/engine.json: unknown keys "
                         f"{sorted(unknown)}, missing "
                         f"{sorted(ENGINE_KEYS - set(eng))}")
    if eng["weights"] not in ("int8", "bfloat16"):
        raise ValueError(f"engine.json weights {eng['weights']!r}: the "
                         "served types are int8 and bfloat16")
    if not isinstance(eng.get("engine_config", {}), dict):
        raise ValueError(f"{config_dir}/engine.json: engine_config must be "
                         "an object of EngineConfig fields")
    return eng


def engine_config_kwargs(eng: dict, decided) -> dict:
    """engine.json's `engine_config` as keyword arguments of the program's
    EngineConfig: every name a field of the dataclass and none of `decided`
    (the arguments `build_engine_config` passes itself: what the eleven
    keys, the seed and the configuration already set); a JSON list becomes
    the tuple the fields hold."""
    from xllm_service_tpu.engine.config import EngineConfig

    extra = eng.get("engine_config", {})
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    decided = set(decided) | {"mesh"}          # from `tp`, below
    unknown = set(extra) - (fields - decided)
    if unknown:
        raise ValueError(
            f"engine.json engine_config: unknown keys {sorted(unknown)} "
            f"(not fields of EngineConfig: {sorted(set(extra) - fields)}; "
            f"decided elsewhere: {sorted(set(extra) & decided)})")
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in extra.items()}


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
    base = dict(
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
    ecfg = EngineConfig(**base, **engine_config_kwargs(eng, base))
    if eng["tp"] > 1:
        ecfg.mesh = MeshConfig(model=eng["tp"])
    return ecfg, eng
