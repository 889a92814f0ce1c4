"""Agent: accept of the dispatched request -> first delta pushed, p50 over
the agent's ring of the last 512 requests (`/stats`.ttft_spans)."""


def read(ctx):
    spans = (ctx.get("agent_stats") or {}).get("ttft_spans") or {}
    if not spans.get("n"):
        return None
    return float(spans["agent_accept_to_first_delta_ms"])
