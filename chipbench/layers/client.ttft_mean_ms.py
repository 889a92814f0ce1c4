"""Client: due time -> first streamed token, mean over the window's
requests, as the client of the master's HTTP port sees it: the same number
as the end-to-end `ttft_ms.mean`, for the cells whose runs spread too
widely to judge it (BENCHMARK.json lists them). Read in a traced run, so
the few seconds of profiling lie inside its window."""


def read(ctx):
    value = (ctx.get("client") or {}).get("ttft_ms.mean")
    return None if value is None else float(value)
