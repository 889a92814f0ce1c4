"""Kernels: share of the chip's published HBM bandwidth that the keys and
values of the live contexts account for during the paged-attention kernel
= context tokens a decode step served (context_token_steps / decode_steps,
`/stats`.engine_trace.recent) x bytes of keys and values per token (2 x
layers x kv heads x head dim x itemsize of the pool, which has the model's
dtype) / the kernel's time per step (`kernel.paged_attn_ms`, the reader
beside this file) / peak bytes/s.

Two sources, and a LOWER BOUND, not the kernel's roofline share: only the
time is the device trace's (the 4 traced seconds); the bytes are a host
counter over the last 30 s of the window (BENCHMARK.json has one `source`
per metric and says `device_trace`, after the denominator). The host counts
a sequence's context at dispatch and not its growth inside a call, and
queries and outputs are left out. So `higher` is better only as far as a
bound tells: a rise says the kernel moved at least that much faster per
byte it had to read. It cannot pass 100."""

import importlib.util
from pathlib import Path

from chipbench import engine_trace, peaks

_kernel = importlib.util.spec_from_file_location(
    "_paged_attn_ms", Path(__file__).with_name("kernel.paged_attn_ms.py"))
ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def kv_bytes_per_token(hf: dict) -> int | None:
    """None for a pool type whose size is not known here."""
    itemsize = ITEMSIZE.get(hf.get("torch_dtype"))
    if itemsize is None:
        return None
    hd = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    return (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"] * hd
            * itemsize)


def read(ctx):
    tokens = engine_trace.ratio(ctx, "context_token_steps", "decode_steps")
    per_token = kv_bytes_per_token(ctx.get("hf") or {})
    if not tokens or not per_token:
        return None
    mod = importlib.util.module_from_spec(_kernel)
    _kernel.loader.exec_module(mod)
    kernel_ms = mod.read(ctx)
    if not kernel_ms:
        return None
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    need = tokens * per_token
    return 100.0 * (need / peak) / (kernel_ms / 1000.0)
