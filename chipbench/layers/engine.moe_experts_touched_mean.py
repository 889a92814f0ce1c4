"""Engine loop: experts that got at least one live row, per expert layer
and decode step, mean over the steps of the last 30 s of the window =
moe_experts_touched / moe_steps / the family's expert layers
(`/stats`.engine_trace.recent; counted on the device by the router, brought
home in each decode call's own result). Of `n_routed_experts`: with n live
rows routing evenly it is E x (1 - (1 - k/E)**n); every expert touched is
9.4 MB a step at this configuration's widths, so at a fixed offered load
fewer is better. Nothing where the program has no such counter or the family
none of the count."""

from chipbench import engine_trace


def read(ctx):
    layers = getattr(getattr(ctx.get("family"), "bytes", None),
                     "moe_layers", None)
    if layers is None or not layers(ctx["hf"]):
        return None
    return engine_trace.ratio(ctx, "moe_experts_touched", "moe_steps",
                              1.0 / layers(ctx["hf"]))
