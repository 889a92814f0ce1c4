"""v5e-64 north-star topology proof: the worker
runs in a fresh process with 64 virtual CPU devices (this suite's own
platform is pinned to 8, so a subprocess is the only way to get there)
and must print every section's OK line. Any mesh-math assumption that
breaks past 8 devices — head/expert/page divisibility at axis size 8,
ring step counts, disjoint-group PD placement — fails this test."""

import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SECTIONS = [
    "OK northstar_dryrun",
    "OK page_shard_divisibility_guard",
    "OK cp8_engine_decode",
    "OK pd_disjoint_device_groups",
]


def test_northstar_topology_worker():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)            # worker pins its own 64
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, str(REPO / "tests" / "northstar_worker.py")],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=str(REPO))
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    for line in SECTIONS:
        assert line in r.stdout, (line, r.stdout[-2000:])
