"""Test harness config.

Engine/sharding tests run on a virtual 8-device CPU mesh (the standard JAX
multi-host test pattern; SURVEY.md §4) — env must be set before jax import.
"""

import os
import sys

# Tests run hermetic on the virtual 8-device CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_native() -> None:
    """`make -C csrc` once per run, before anything is collected: the
    modules that load libhotcore.so / libblockhash.so decide at import
    whether their tests exist, so a library built mid-run (by the tests
    that need the coordination server) came too late for them and the
    suite's count depended on the tree's history. Without a C toolchain,
    or where the build fails, the pure-Python fallbacks stay and those
    tests skip, as before; a tree whose libraries are newer than their
    sources is left alone."""
    import shutil
    import subprocess

    csrc = os.path.join(REPO, "csrc")
    built = [os.path.join(csrc, f) for f in
             ("coordination_server", "libblockhash.so", "libhotcore.so")]
    sources = [os.path.join(csrc, f) for f in os.listdir(csrc)
               if f.endswith((".c", ".cpp")) or f == "Makefile"]
    if all(map(os.path.exists, built)) and (
            min(map(os.path.getmtime, built))
            > max(map(os.path.getmtime, sources))):
        return
    if shutil.which("make") and shutil.which(os.environ.get("CC", "cc")):
        try:
            subprocess.run(["make", "-k", "-C", csrc], capture_output=True,
                           timeout=600, check=False)
        except (subprocess.TimeoutExpired, OSError):
            pass


# The xdist controller (or a single-process run) builds; its workers start
# after this module is imported there and find the libraries built.
if "PYTEST_XDIST_WORKER" not in os.environ:
    _build_native()

# Persistent XLA compile cache: the suite is dominated by recompiles of
# the same tiny-model programs across test processes. Same rule as the
# engine (utils.enable_persistent_compile_cache): the directory
# JAX_COMPILATION_CACHE_DIR names, else the fixed one in the checkout.
from xllm_service_tpu.utils import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

import pytest  # noqa: E402

from xllm_service_tpu.coordination.memory import MemoryStore  # noqa: E402
from xllm_service_tpu.devtools import lifecycle as _xlifecycle  # noqa: E402
from xllm_service_tpu.devtools import locks as _xlocks  # noqa: E402
from xllm_service_tpu.devtools import ownership as _xownership  # noqa: E402
from xllm_service_tpu.devtools import rcu as _xrcu  # noqa: E402


@pytest.fixture()
def store():
    """A fresh coordination 'cluster' per test."""
    st = MemoryStore(expiry_tick_s=0.02)
    yield st
    st.close()


@pytest.fixture(autouse=True)
def _instrumented_lock_guard():
    """Under XLLM_LOCK_DEBUG=1 every test doubles as a race/deadlock
    detector: any lock-order inversion or lock-held-across-I/O recorded by
    the instrumented locks (devtools/locks.py) during the test fails it —
    so the existing chaos drills moonlight as a race detector."""
    if not _xlocks.debug_enabled():
        yield
        return
    _xlocks.reset_violations()
    yield
    vs = _xlocks.violations()
    assert not vs, ("instrumented-lock violations:\n"
                    + "\n".join(str(v) for v in vs))


@pytest.fixture(autouse=True)
def _state_ownership_guard():
    """Under XLLM_STATE_DEBUG=1 every test doubles as an attribute-race
    detector: registered classes (devtools/ownership.py
    STATE_DISCIPLINES) record (thread role, locks held) for every write
    and any discipline violation recorded during the test fails it — so
    the chaos, multimaster-kill and tier drills moonlight as a
    shared-state ownership verifier, mirroring the lock and RCU guards
    around this one."""
    if not _xownership.debug_enabled():
        yield
        return
    _xownership.reset_violations()
    yield
    vs = _xownership.violations()
    assert not vs, ("state-ownership violations:\n"
                    + "\n".join(str(v) for v in vs))


@pytest.fixture(autouse=True)
def _leak_guard():
    """Under XLLM_LEAK_DEBUG=1 every test doubles as a resource-leak
    detector: instrumented acquire/release pairs (devtools/lifecycle.py
    EFFECT_PAIRS) keep per-pair balance counters with acquisition
    stacks. A double-release or metric-series resurrection recorded
    during the test fails it, and so does a nonzero teardown balance on
    a `strict` pair (an admission slot or flight-recorder context
    provider that leaked) — the runtime mirror of xlint's pair-release/
    pair-once/pair-evict rules, following the lock/state/RCU guards
    around this one."""
    if not _xlifecycle.debug_enabled():
        yield
        return
    _xlifecycle.reset_violations()
    _xlifecycle.reset_balances()
    yield
    vs = _xlifecycle.violations() + _xlifecycle.strict_imbalances()
    assert not vs, ("lifecycle pair violations:\n"
                    + "\n".join(str(v) for v in vs))


@pytest.fixture(autouse=True)
def _rcu_freeze_guard():
    """Under XLLM_RCU_DEBUG=1 every test doubles as a snapshot-race
    detector: RCU publications are deep-frozen (devtools/rcu.py) and any
    in-place mutation recorded during the test fails it — even when the
    raising path was swallowed by a broad except. The chaos, multimaster
    kill, and tier-transition drills all moonlight as detectors this
    way, mirroring the instrumented-lock guard above."""
    if not _xrcu.debug_enabled():
        yield
        return
    _xrcu.reset_violations()
    yield
    vs = _xrcu.violations()
    assert not vs, ("rcu deep-freeze violations:\n"
                    + "\n".join(str(v) for v in vs))
