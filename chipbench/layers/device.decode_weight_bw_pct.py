"""Device: share of the chip's published HBM bandwidth that the weight
stream alone accounts for during a decode step = bytes of weights one step
must read (bytes_model.py) / peak bytes/s / measured step time. Keys and
values are left out, so it is a lower bound of the step's roofline share
and cannot pass 100."""

import importlib.util
from pathlib import Path

from chipbench import bytes_model, peaks

_step = importlib.util.spec_from_file_location(
    "_decode_step", Path(__file__).with_name("prog.decode_step_ms.py"))


def read(ctx):
    mod = importlib.util.module_from_spec(_step)
    _step.loader.exec_module(mod)
    step_ms = mod.read(ctx)
    if not step_ms:
        return None
    peak = peaks.lookup(ctx["device"]["kind"])["hbm_bytes_per_s"]
    need = bytes_model.decode_weight_stream_bytes(ctx["hf"],
                                                  ctx["engine"]["weights"])
    return 100.0 * (need / peak) / (step_ms / 1000.0)
