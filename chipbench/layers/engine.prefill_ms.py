"""Engine loop: prefill execution per admission, p50 over the last 512
(`/stats`.ttft_spans.engine_prefill_ms): tens of ms when the prefix cache
serves the prompt, the whole prefill when not."""


def read(ctx):
    spans = (ctx.get("agent_stats") or {}).get("ttft_spans") or {}
    if not spans.get("n"):
        return None
    return float(spans["engine_prefill_ms"])
