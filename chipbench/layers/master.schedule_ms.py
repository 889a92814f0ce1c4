"""Master hot path, schedule stage (executor hop + Scheduler.schedule):
p50 over the master's recent-sample window, as `/admin/hotpath` reports it
at the end of the measured window."""


def read(ctx):
    stage = (ctx.get("hotpath") or {}).get("stages", {}).get("schedule")
    if not stage or not stage.get("n"):
        return None
    return float(stage["p50"])
