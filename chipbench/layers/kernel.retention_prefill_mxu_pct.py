"""Kernels: share of the chip's published bfloat16 peak that the
retention-prefill kernel reaches on the products it must do for the tokens
its calls HELD = sum over its events in the trace of the family's count
(`ctx["family"].bytes.retention_prefill_flops(hf, tokens)` over the layers:
one event is one layer's, so a layer's share of it) / the events' summed
seconds / peak FLOP/s. The count is of the MXU's products alone (`bytes.py`
says what it leaves out).

The tokens of an event: inside a `prefill_chunk` execution the C of its name
(`_retention_prefill_impl_c<C>`), all of them a prompt's (the engine's one
rule sends a chunk only where more than a chunk is left). Inside a
`prefill_install` execution C is the bucket, of which the prompt's last
tokens fill a part; which part is not in the trace, so an install's events
count C x the share of the installs' rows that were a prompt's over the last
30 s of the window (`/stats`.engine_trace.recent: `prompt_tokens` less
`prefix_hit_tokens` and `prefill_chunk_tokens`, over that plus
`prefill_padded_tokens`). A bucket's padding is multiplied like any token and
is not work the prompt asked for: it is left out, so the share errs LOW by
what the kernel spent on it. Without the counters the metric is left out."""

from pathlib import Path

from chipbench import engine_trace, harness, peaks

_ms = harness.load_file(
    Path(__file__).with_name("kernel.retention_prefill_ms.py"))


def install_valid_share(recent: dict) -> float | None:
    """Rows of the install programs that held a prompt's token, as a share
    of the rows they computed; None without the counters or an install."""
    if not {"prompt_tokens", "prefill_padded_tokens",
            "prefill_chunk_tokens"} <= set(recent):
        return None
    valid = (recent["prompt_tokens"] - recent.get("prefix_hit_tokens", 0)
             - recent["prefill_chunk_tokens"])
    rows = valid + recent["prefill_padded_tokens"]
    return valid / rows if rows > 0 and valid >= 0 else None


def read(ctx):
    family = ctx.get("family")
    if not ctx.get("trace") or not family:
        return None
    flops = getattr(family.bytes, "retention_prefill_flops", None)
    share = install_valid_share(engine_trace.recent(ctx) or {})
    if flops is None or share is None:
        return None
    layers = ctx["hf"]["num_hidden_layers"]
    need = seconds = 0.0
    for prog, ev in _ms.kernel_events(ctx["trace"]):
        held = _ms.tokens_of(ev) * (share if prog == "prefill_install" else 1)
        need += flops(ctx["hf"], held) / layers
        seconds += ev["dur"]
    if not seconds:
        return None
    peak = peaks.lookup(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * (need / peak) / seconds
