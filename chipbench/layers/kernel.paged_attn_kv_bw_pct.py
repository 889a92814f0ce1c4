"""Kernels: share of the chip's published HBM bandwidth that the keys and
values of the live contexts account for during the paged-attention kernel
= context tokens a decode step served (context_token_steps / decode_steps,
`/stats`.engine_trace.recent) x bytes of keys and values per token (the
configuration's family counts them: `ctx["family"].bytes.kv_bytes_per_token`,
which knows in how many layers a token holds any) / the kernel's time per
step (`kernel.paged_attn_ms`, the reader beside this file) / peak bytes/s.

Two sources, and a LOWER BOUND, not the kernel's roofline share: only the
time is the device trace's (the 4 traced seconds); the bytes are a host
counter over the last 30 s of the window (BENCHMARK.json has one `source`
per metric and says `device_trace`, after the denominator). The host counts
a sequence's context at dispatch and not its growth inside a call, and
queries and outputs are left out. So `higher` is better only as far as a
bound tells: a rise says the kernel moved at least that much faster per
byte it had to read. It cannot pass 100."""

from pathlib import Path

from chipbench import engine_trace, harness, peaks


def read(ctx):
    tokens = engine_trace.ratio(ctx, "context_token_steps", "decode_steps")
    if not tokens or not ctx.get("family"):
        return None
    per_token = ctx["family"].bytes.kv_bytes_per_token(ctx["hf"])
    if not per_token:
        return None
    kernel_ms = harness.load_file(
        Path(__file__).with_name("kernel.paged_attn_ms.py")).read(ctx)
    if not kernel_ms:
        return None
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    need = tokens * per_token
    return 100.0 * (need / peak) / (kernel_ms / 1000.0)
