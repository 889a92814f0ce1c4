#!/usr/bin/env python3
"""The one process of a run that holds the chip: the program's own
`EngineAgent`, started as `engine/agent.py main()` starts it, for a
configuration given as a directory (config.json + engine.json) instead of a
name from the agent's factory table.

Because only the chip's holder can see the chip, it also answers a few
commands from run.py, one JSON object per line on stdin, each answered by
one line `CHIPBENCH {json}` on stdout:

  {"cmd": "mark"}                     counters now: programs built (compiled or
                                      loaded), of those loaded from the cache
  {"cmd": "trace_start", "dir": d}    jax.profiler.start_trace(d)
  {"cmd": "trace_stop"}               jax.profiler.stop_trace()
  {"cmd": "report"}                   device line, memory peak, counters
  {"cmd": "exit"}                     stop the agent, leave

Nothing here changes what the engine computes: weights come from the
configuration's family (harness.Family: seeded, made on the device in one
call, in the served type) and go in through `EngineAgent(params=...)`, the
door a checkpoint loader uses.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import traceback
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def say(**kw) -> None:
    sys.stdout.write("CHIPBENCH " + json.dumps(kw) + "\n")
    sys.stdout.flush()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-dir", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--coordination-addr", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--model-id", required=True)
    ap.add_argument("--tokenizer-path", required=True)
    ap.add_argument("--platform", required=True,
                    help="what JAX must hold; anything else is a failure")
    ap.add_argument("--search", action="append", required=True,
                    help="BENCHMARK.json's paths: where a family is found")
    args = ap.parse_args()

    import jax

    # Every XLA compilation and every executable loaded from the
    # persistent cache, counted by name for the life of the process; each
    # program built is also kept by name, so that a run refused for
    # building one inside its window says which.
    counters: dict[str, int] = {}
    built: list = []
    lock = threading.Lock()

    def count(name, *a, **kw):
        with lock:
            counters[name] = counters.get(name, 0) + 1
            if "backend_compile" in name:
                # Who asked for it: the program's frames on this thread.
                where = [f"{Path(f.filename).name}:{f.lineno}:{f.name}"
                         for f in traceback.extract_stack()
                         if "xllm_service_tpu" in f.filename][-4:]
                built.append([time.time(), str(kw.get("fun_name", "?")),
                              float(a[0]) if a else None,
                              threading.current_thread().name, where])

    # Why a jitted function was traced again (stderr): what explains a
    # program built inside a window it was warmed up for.
    jax.config.update("jax_explain_cache_misses", True)
    jax.monitoring.register_event_duration_secs_listener(count)
    jax.monitoring.register_event_listener(count)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(event="devices", **device)
    if device["platform"] != args.platform:
        say(event="fatal", error=f"JAX holds {device}, not {args.platform}")
        return 3

    from xllm_service_tpu.engine.agent import AgentConfig, EngineAgent
    from xllm_service_tpu.utils import enable_persistent_compile_cache

    from chipbench import harness
    from chipbench.engine_setup import build_engine_config

    cache_dir = enable_persistent_compile_cache()
    config_dir = Path(args.config_dir)
    hf = json.loads((config_dir / "config.json").read_text())
    family = harness.family_of(args.search, hf)
    weights = family.weights
    ecfg, eng = build_engine_config(config_dir, args.seed, args.model_id)
    if eng["replicas"] > len(devs) or eng["tp"] > len(devs):
        say(event="fatal", error=f"engine.json asks for tp {eng['tp']} x "
            f"replicas {eng['replicas']} on {len(devs)} device(s)")
        return 3

    t0 = time.monotonic()
    out_shardings = None
    if ecfg.mesh is not None:
        from jax.sharding import NamedSharding

        from xllm_service_tpu.models import get_model_family
        from xllm_service_tpu.parallel.mesh import build_mesh
        from xllm_service_tpu.parallel.sharding import tree_specs

        mesh = build_mesh(ecfg.mesh, devices=devs[:ecfg.mesh.num_devices()])
        specs = tree_specs(weights.param_shapes(hf, eng["weights"]),
                           get_model_family(ecfg.model_family).sharding_rules)
        out_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    params = weights.make_params(args.seed, hf, eng["weights"], out_shardings)
    t_weights = time.monotonic() - t0

    t0 = time.monotonic()
    agent = EngineAgent(
        ecfg, AgentConfig(host="127.0.0.1", port=args.port,
                          coordination_addr=args.coordination_addr,
                          model_id=args.model_id,
                          tokenizer_path=args.tokenizer_path,
                          dp_size=eng["replicas"]),
        params=params)
    del params
    agent.start()
    say(event="started", weights_s=t_weights,
        engine_s=time.monotonic() - t0, compile_cache=cache_dir,
        family=family.name or "default", weights_file=weights.__file__,
        engine_config=eng.get("engine_config", {}))

    def snapshot() -> dict:
        with lock:
            c = dict(counters)
        return {
            "compilations": sum(v for k, v in c.items()
                                if "backend_compile" in k),
            "cache_loads": sum(v for k, v in c.items()
                               if k.endswith("cache_hits")),
            "events": c,
            "built": list(built[-64:]),
        }

    tracing = False
    try:
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            msg = json.loads(line)
            cmd = msg.get("cmd")
            if cmd == "mark":
                say(event="mark", t=time.time(), **snapshot())
            elif cmd == "trace_start":
                jax.profiler.start_trace(msg["dir"])
                tracing = True
                say(event="trace_started", t=time.time())
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                tracing = False
                say(event="trace_stopped", t=time.time())
            elif cmd == "report":
                peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                         for d in devs]
                known = [p for p in peaks if p is not None]
                say(event="report", device=device,
                    memory_peak_bytes=max(known) if known else None,
                    **snapshot())
            elif cmd == "exit":
                break
            else:
                say(event="error", error=f"unknown command {cmd!r}")
    finally:
        if tracing:
            jax.profiler.stop_trace()
        agent.stop()
    say(event="bye")
    sys.stdout.flush()
    # Daemon threads of the engine and the HTTP server may still hold the
    # interpreter; the run is over and the chip must be free now.
    os._exit(0)


if __name__ == "__main__":
    sys.exit(main())
