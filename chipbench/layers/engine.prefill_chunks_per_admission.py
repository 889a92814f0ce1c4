"""Engine loop: prefill programs an admission took = (standalone
`prefill_chunk` calls + admissions) / admissions over the last 30 s of the
window (`/stats`.engine_trace.recent `prefill_chunks` and `admissions`: every
admission ends in one install program, and a prompt longer than
`prefill_chunk_tokens` runs the chunks before it, each handing the slot's
state to the next). 1 where nothing is chunked; a program without the counter
reports nothing."""

from chipbench import engine_trace


def read(ctx):
    r = engine_trace.recent(ctx)
    if not r or "prefill_chunks" not in r or not r.get("admissions"):
        return None
    return (r["prefill_chunks"] + r["admissions"]) / r["admissions"]
