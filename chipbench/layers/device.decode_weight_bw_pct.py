"""Device: share of the chip's published HBM bandwidth that the weight
stream alone accounts for during a decode step = bytes of weights one step
must read (the configuration's family counts them:
`ctx["family"].bytes.decode_weight_stream_bytes`) / peak bytes/s / measured
step time. Keys and values are left out, so it is a lower bound of the
step's roofline share and cannot pass 100."""

from pathlib import Path

from chipbench import harness, peaks


def read(ctx):
    step_ms = harness.load_file(
        Path(__file__).with_name("prog.decode_step_ms.py")).read(ctx)
    if not step_ms or not ctx.get("family"):
        return None
    need = ctx["family"].bytes.decode_weight_stream_bytes(
        ctx["hf"], ctx["engine"]["weights"])
    if not need:
        return None
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (step_ms / 1000.0)
