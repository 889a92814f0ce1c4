"""Engine loop: submit -> admission, p50 over the engine's ring of the last
512 admissions (`/stats`.ttft_spans.engine_queue_ms)."""


def read(ctx):
    spans = (ctx.get("agent_stats") or {}).get("ttft_spans") or {}
    if not spans.get("n"):
        return None
    return float(spans["engine_queue_ms"])
