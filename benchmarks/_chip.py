"""The device micro-benches measure the TPU: they fail without one."""

from __future__ import annotations

import os
import sys


def require_tpu() -> dict:
    """Exit non-zero unless JAX holds a TPU; else the device as JAX reports
    it, for the result record. A CPU number is never printed under a
    device metric's name."""
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        sys.exit("JAX_PLATFORMS=cpu: this bench measures the TPU and prints "
                 "nothing without one")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"jax found platform {dev.platform!r}, not a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
