"""Real-checkpoint end-to-end drill: load a REAL
published HF checkpoint through models/hf_config + models/loader, serve
it through the FULL stack (HTTP client → master → engine agent →
engine), and assert the served greedy continuation token-matches
`transformers` greedy generation on the same weights.

    python scripts/real_ckpt_drill.py [--ckpt DIR] [--tokens N]

Checkpoint resolution, in order:
  1. --ckpt / XLLM_REAL_CKPT (a local HF model directory);
  2. huggingface_hub.snapshot_download(XLLM_REAL_CKPT_REPO, default
     Qwen/Qwen2.5-0.5B) — attempted with a deadline; in a zero-egress
     sandbox this fails fast and the drill records the attempt.

Emits ONE JSON line either way:

    {"metric": "real_ckpt_parity", "backend": ..., "ok": true,
     "model_type": "qwen2", "tokens_matched": 32, "tokens_total": 32}
    {"metric": "real_ckpt_parity", "backend": ...,
     "skipped": "checkpoint unavailable: ..."}

`skipped` (not `error`) says the checkpoint could not be had (no
network); a real parity MISMATCH sets ok=false AND `error` and exits 1,
as does any failure of the drill itself.

The hermetic test (tests/test_hf_parity.py) drives run_drill() on
synthetic checkpoints, so the full machinery — config mapping, loader,
serve stack, transformers comparison — is CPU-proven even while the
sandbox has no network; pointing it at a real dir exercises the
identical path.

Reference analog: the reference boots its fleet straight from HF model
dirs (`docs/en/getting_started.md:73-90`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

DEFAULT_REPO = "Qwen/Qwen2.5-0.5B"
PROMPT = "The capital of France is"


def resolve_checkpoint(explicit: str | None) -> tuple[str | None, str]:
    """Return (ckpt_dir, note). ckpt_dir None = unavailable."""
    cand = explicit or os.environ.get("XLLM_REAL_CKPT", "")
    if cand:
        if (Path(cand) / "config.json").exists():
            return cand, f"local dir {cand}"
        return None, f"XLLM_REAL_CKPT={cand} has no config.json"
    repo = os.environ.get("XLLM_REAL_CKPT_REPO", DEFAULT_REPO)
    # Hard deadline around the whole download: hub retry/DNS stalls can
    # far exceed etag_timeout in a zero-egress sandbox, and the sweep
    # step must record "skipped", not hang into its kill timeout.
    deadline_s = float(os.environ.get("XLLM_CKPT_DOWNLOAD_DEADLINE_S",
                                      "600"))
    import threading
    box: dict = {}

    def _download():
        try:
            from huggingface_hub import snapshot_download
            box["dir"] = snapshot_download(repo, etag_timeout=10)
        except Exception as e:  # noqa: BLE001 — zero-egress sandbox
            box["err"] = f"{type(e).__name__}: {e}"[:250]

    # Daemon thread: an abandoned stalled download must not block
    # process exit after the skipped line prints.
    t = threading.Thread(target=_download, daemon=True)
    t.start()
    t.join(timeout=deadline_s)
    if "dir" in box:
        return box["dir"], f"downloaded {repo}"
    if "err" in box:
        return None, (f"checkpoint unavailable: download of {repo} "
                      f"failed ({box['err']})")
    return None, (f"checkpoint unavailable: download of {repo} hit "
                  f"the {deadline_s:.0f}s deadline")


def hf_greedy_ids(ckpt_dir: str, prompt_ids: list[int],
                  max_new: int) -> list[int]:
    """transformers greedy continuation (float32, EOS disabled so the
    comparison covers exactly max_new tokens)."""
    import torch
    from transformers import AutoModelForCausalLM

    model = AutoModelForCausalLM.from_pretrained(
        ckpt_dir, torch_dtype=torch.float32)
    model.eval()
    with torch.no_grad():
        out = model.generate(
            torch.tensor([prompt_ids]), max_new_tokens=max_new,
            do_sample=False, eos_token_id=None, pad_token_id=0)
    return out[0, len(prompt_ids):].tolist()


def run_drill(ckpt_dir: str, prompt: str = PROMPT, max_new: int = 32,
              max_context: int = 1024) -> dict:
    """Serve `ckpt_dir` through the full stack and compare the greedy
    continuation against transformers. Importable — the hermetic test
    runs this exact function on synthetic checkpoints."""
    import jax.numpy as jnp
    import requests

    from xllm_service_tpu.common.config import ServiceOptions
    from xllm_service_tpu.common.types import InstanceType
    from xllm_service_tpu.coordination.memory import (InMemoryCoordination,
                                                      MemoryStore)
    from xllm_service_tpu.engine.agent import AgentConfig, EngineAgent
    from xllm_service_tpu.engine.config import EngineConfig
    from xllm_service_tpu.master import Master
    from xllm_service_tpu.models.hf_config import (load_checkpoint,
                                                   model_config_from_hf)
    from xllm_service_tpu.tokenizer import TokenizerFactory

    import jax

    backend = jax.default_backend()
    tok = TokenizerFactory.create_tokenizer(str(ckpt_dir))
    prompt_ids = tok.encode(prompt)
    # transformers reference FIRST: the torch model frees before the JAX
    # param tree materializes, halving peak host RAM (both are float32
    # full copies of the checkpoint).
    hf_ids = hf_greedy_ids(ckpt_dir, prompt_ids, max_new)

    # float32 end to end, and matmuls pinned to true-f32 accumulation:
    # on TPU the default precision runs f32 matmuls as bf16 passes,
    # which can flip greedy near-ties vs transformers' float32 CPU math.
    prev_prec = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = model_config_from_hf(ckpt_dir, dtype=jnp.float32,
                               max_context_len=max_context)
    params = load_checkpoint(ckpt_dir, cfg)

    store = MemoryStore(expiry_tick_s=0.05)
    opts = ServiceOptions(host="127.0.0.1", http_port=0, rpc_port=0,
                          lease_ttl_s=2.0, sync_interval_s=0.3,
                          reconcile_interval_s=0.1,
                          tokenizer_path=str(ckpt_dir))
    master = Master(opts, coord=InMemoryCoordination(store))
    master.start()
    agent = None
    try:
        model_id = Path(ckpt_dir).name or "real-ckpt"
        # Page-aligned shapes (EngineConfig.validate): one bucket that
        # fits the prompt, a max_seq that fits prompt+continuation.
        align = 16
        b1 = max(32, -(-len(prompt_ids) // align) * align)
        max_seq = min(cfg.max_context_len,
                      max(256, b1 + -(-max_new // align) * align + align))
        ecfg = EngineConfig(
            model_id=model_id, model=cfg, model_family=cfg.name,
            num_pages=2 * max_seq // align + 32, page_size=align,
            hash_block_size=32, max_batch_size=2,
            max_seq_len=max_seq,
            prefill_buckets=(b1, max_seq) if b1 < max_seq else (max_seq,))
        agent = EngineAgent(
            ecfg,
            AgentConfig(host="127.0.0.1", model_id=model_id,
                        instance_type=InstanceType.MIX,
                        tokenizer_path=str(ckpt_dir),
                        heartbeat_interval_s=0.3, lease_ttl_s=2.0),
            coord=InMemoryCoordination(store), params=params)
        agent.start()

        import time
        deadline = time.time() + 60
        while time.time() < deadline:
            if master.scheduler.instance_mgr.get_instance_meta(agent.name):
                break
            time.sleep(0.1)
        else:
            raise TimeoutError("engine instance never registered")

        r = requests.post(
            f"http://127.0.0.1:{master.http_port}/v1/completions",
            json={"model": model_id, "prompt": prompt,
                  "max_tokens": max_new, "temperature": 0,
                  "ignore_eos": True},
            timeout=600)
        r.raise_for_status()
        served_text = r.json()["choices"][0]["text"]
    finally:
        if agent is not None:
            agent.stop()
        master.stop()
        store.close()
        jax.config.update("jax_default_matmul_precision", prev_prec)

    # Both sides decode through the SAME tokenizer: equal ids ⇒ equal
    # text, and a text mismatch pinpoints the first diverging token.
    hf_text = tok.decode(hf_ids)
    matched = 0
    for i in range(1, len(hf_ids) + 1):
        if served_text.startswith(tok.decode(hf_ids[:i])):
            matched = i
    ok = served_text == hf_text
    out = {"metric": "real_ckpt_parity", "backend": backend, "ok": ok,
           "model_type": cfg.name, "tokens_total": len(hf_ids),
           "tokens_matched": matched,
           "prompt_tokens": len(prompt_ids)}
    if not ok:
        out["error"] = (f"greedy divergence after {matched}/{len(hf_ids)} "
                        f"tokens: served={served_text[:120]!r} "
                        f"hf={hf_text[:120]!r}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--prompt", default=PROMPT)
    args = ap.parse_args()

    import jax

    backend = jax.default_backend()
    ckpt, note = resolve_checkpoint(args.ckpt)
    if ckpt is None:
        print(json.dumps({"metric": "real_ckpt_parity",
                          "backend": backend, "skipped": note}))
        return
    result = run_drill(ckpt, prompt=args.prompt, max_new=args.tokens)
    result["checkpoint"] = note
    print(json.dumps(result))
    if not result["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
